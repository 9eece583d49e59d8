"""A new family leaves the other families' programs as they were: the dense
arm's, ``hybrid``'s, ``latent``'s and ``swa``'s full-size serving programs
(``tools/commit_probe.py`` ``LOWERED_PROGRAMS``: InternLM2-1.8B's two
prefills and its decode chunk, Mellum's, Nemotron-3-Nano's and DeepSeek-V2's
prefill, under the chip's selections) lower for a TPU to the text they lower
to at PR 49's tree (commit b2b7cc0; ``tests/fixtures/
lowered_programs_pr49.json`` holds a hash a program, made by the same walk on
that tree, the serialised Mosaic bodies masked: they carry the checkout's
path). The tiny presets' programs under ``"xla"`` are
``tests/test_commit_programs.py``'s. What a cell compiles is what it pays in
``setup_s`` (PR 48's refusal)."""

import hashlib
import importlib.util
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "fixtures",
                       "lowered_programs_pr49.json")) as f:
    PARENTS = json.load(f)
_probe = []


def probe():
    if not _probe:
        spec = importlib.util.spec_from_file_location(
            "commit_probe", os.path.join(ROOT, "tools", "commit_probe.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _probe.append(module)
    return _probe[0]


def masked_hash(text: str) -> str:
    return hashlib.sha256(re.sub(
        r"[A-Za-z0-9+/=]{200,}", "<mosaic>", text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("program", sorted(PARENTS))
def test_the_other_families_programs_lower_to_the_parents_text(program):
    name, shape = program.split(":")
    B, T = map(int, shape.split("x"))
    cp = probe()
    assert (name, B, T) in cp.LOWERED_PROGRAMS
    cp.lowered_program(name, B, T)
    assert masked_hash(cp.LOWERED_TEXT[0]) == PARENTS[program]


def test_the_fixture_names_a_program_of_each_other_family():
    assert {p.split(":")[0] for p in PARENTS} == {
        "internlm2", "mellum", "nemotron", "deepseek"}


def test_the_family_s_own_prefill_lowers_for_a_tpu_with_its_kernel():
    cp = probe()
    cp.lowered_program("evabyte", 1, 4096)
    text = cp.LOWERED_TEXT[0]
    assert "eva_flash" in text and "pool_commit" in text
    assert "flash_prefill" not in text
