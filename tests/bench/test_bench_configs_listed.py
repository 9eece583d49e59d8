"""Every configuration file is a configuration of the benchmark, and every
configuration has a cell: a PR that adds files under ``bench/configs/`` and
no entry in ``BENCHMARK.json`` is never measured (PR 38 was refused so)."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
FILES = sorted(
    f"bench/configs/{name}"
    for name in os.listdir(os.path.join(ROOT, "bench", "configs"))
    if name.endswith(".json"))


@pytest.mark.parametrize("path", FILES)
def test_a_configuration_file_is_listed(path):
    assert path in [c["file"] for c in BENCH["configs"]]


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_a_configuration_has_a_cell_and_its_file(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f)["name"] == config
    assert any(w["config"] == config for w in BENCH["workloads"])
