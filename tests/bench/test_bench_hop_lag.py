"""The three readers of PR 36 (``bench/layer_metrics/``): ``idle_hop_ms_s``,
``idle_loop_lag_ms_s`` (the host trace's idle by innermost span) and
``loop_lag_ms_s`` (the flight samples' ``resume_lag_ms``): on a known answer
(hand-written samples, ``fixtures/flight/hop_lag.samples.json``, and a
hand-built span reduction), on an empty window, on a parent's samples and
spans (nothing, never an error), and their entries in ``BENCHMARK.json``.
The samples' ``gap_ms`` and ``program_ms`` have no reader here: they are
the dispatch thread's view of the device, a lower and an upper bound
(``docs/OBSERVABILITY.md``), and stay with ``/flight/summary``."""

import json
import os

import pytest

from lib import hosttrace, observe

HERE = os.path.dirname(__file__)
ROOT = os.path.join(HERE, "..", "..")
BENCH = os.path.join(ROOT, "bench")
MS = 1e6  # nanoseconds

SPAN_READERS = ("idle_hop_ms_s", "idle_loop_lag_ms_s")
COUNTER_READERS = ("loop_lag_ms_s",)
ENTRIES = {
    "idle_hop_ms_s": ("ms/s", "program_span", "gateway, topic, agent runner",
                      "out_tok_s"),
    "idle_loop_lag_ms_s": ("ms/s", "program_span", "admission and scheduler",
                           "out_tok_s"),
    "loop_lag_ms_s": ("ms/s", "program_counter", "admission and scheduler",
                      "out_tok_s"),
}


def metric(name):
    return observe.load_metric(observe.find("layer_metrics", name, [BENCH]))


@pytest.fixture(scope="module")
def windows():
    path = os.path.join(HERE, "fixtures", "flight", "hop_lag.samples.json")
    with open(path) as f:
        return json.load(f)


# -- the span reduction, built by hand ------------------------------------


def span(name, thread, start_ms, end_ms, **meta):
    return {"name": name, "thread": thread, "start_ns": start_ms * MS,
            "end_ns": end_ms * MS, "meta": meta}


#: One idle gap of the device, 100-116 ms: a prefill program has ended and
#: the next key split has not been issued. The loop (thread 0) holds
#: ``ls.prefill.fetch`` over its await from 90 to 113; the dispatch thread
#: (1) is blocked under ``ls.prefill.wait`` until 102 (its wake and the
#: transfer); then the coroutine is ready and waits for its turn while a
#: reader's wake (104-107) and a gateway's send (107-111) run; from 111 to
#: 113 nobody named runs; the engine's own emit is 113-115 and the next
#: dispatch's span opens at 115.5.
CHANGE_SPANS = [
    span("ls.prefill.fetch", 0, 90, 113, seq=7),
    span("ls.prefill.wait", 1, 90.5, 102, seq=7),
    span("ls.hop.topic", 0, 104, 107),
    span("ls.hop.gw.send", 0, 107, 111, records=2),
    span("ls.prefill.emit", 0, 113, 115, rows=3),
    span("ls.prefill.dispatch", 0, 115.5, 118, seq=8),
]
GAPS = [(100 * MS, 116 * MS, "jit__prefill_-_jit__threefry_split")]
#: the same instants as a program from before the spans sees them: the held
#: span is the only one over the wait, the tenants run under no name
PARENT_SPANS = [
    span("ls.prefill.fetch", 0, 90, 113, seq=7),
    span("ls.prefill.emit", 0, 113, 115, rows=3),
    span("ls.prefill.dispatch", 0, 115.5, 118, seq=8),
]


def traced(spans, window_s=4.0):
    """An ``obs`` whose host-trace reduction is ``spans`` against ``GAPS``."""
    return {"trace": {"window_s": window_s},
            "hosttrace": {"spans": spans, "clock_skew_ns": 0.0,
                          "idle": hosttrace.attribute(GAPS, spans),
                          "scopes": {"total_s": 0.0, "by_scope": {},
                                     "unscoped": {}}}}


def test_the_innermost_span_names_each_part_of_the_gap():
    by_span = hosttrace.attribute(GAPS, CHANGE_SPANS)["by_span"]
    assert {k: round(v * 1e3, 6) for k, v in by_span.items()} == {
        "ls.prefill.wait": 2.0,      # the dispatch thread's wake and transfer
        "ls.prefill.fetch": 4.0,     # the coroutine's turn: 102-104, 111-113
        "ls.hop.topic": 3.0, "ls.hop.gw.send": 4.0,
        "ls.prefill.emit": 2.0, "ls.prefill.dispatch": 0.5,
        "none": 0.5,                 # 115-115.5
    }
    old = hosttrace.attribute(GAPS, PARENT_SPANS)["by_span"]
    assert round(old["ls.prefill.fetch"] * 1e3, 6) == 13.0   # all one number


@pytest.mark.parametrize("name, answer", [
    ("idle_hop_ms_s", 7.0 / 4.0), ("idle_loop_lag_ms_s", 4.0 / 4.0),
    # what the spans took leaves the accepted readers, the held names stay
    ("idle_prefill_host_ms_s", (2.0 + 4.0 + 2.0 + 0.5) / 4.0),
    ("idle_attributed_share", 100 * 15.5 / 16.0),
])
def test_the_span_readers_on_the_hand_built_reduction(name, answer):
    assert metric(name)["read"](traced(CHANGE_SPANS)) == pytest.approx(answer)


@pytest.mark.parametrize("name", SPAN_READERS)
@pytest.mark.parametrize("obs", [
    {}, {"trace": None}, {"trace": {"window_s": 4.0}, "hosttrace": None},
    traced([]), traced(PARENT_SPANS),
    {**traced(CHANGE_SPANS), "trace": {"window_s": 0}},
], ids=["empty", "untraced", "no-trace-file", "no-spans", "parent",
        "no-window"])
def test_the_span_readers_give_nothing_where_nothing_is_read(name, obs):
    assert metric(name)["read"](obs) is None


def test_a_traced_change_with_no_idle_under_the_names_reads_zero():
    busy = {"trace": {"window_s": 4.0},
            "hosttrace": {"spans": CHANGE_SPANS, "clock_skew_ns": 0.0,
                          "idle": hosttrace.attribute([], CHANGE_SPANS)}}
    for name in SPAN_READERS:
        assert metric(name)["read"](busy) == 0.0


# -- the counters' readers, on the samples ---------------------------------


@pytest.mark.parametrize("name, answer", [
    ("loop_lag_ms_s", 30.0 / 2.0),                    # 30 ms of lag in 2.0 s
])
def test_the_counter_readers_on_the_fixture(windows, name, answer):
    read = metric(name)["read"]
    assert read({"samples": windows["change"]}) == pytest.approx(answer)
    # untraced runs read them too: no part of the trace is consulted
    assert read({"samples": windows["change"], "trace": None}) == pytest.approx(answer)


@pytest.mark.parametrize("name", COUNTER_READERS)
@pytest.mark.parametrize("window", [
    "nothing", "none", "empty", "parent", "stall-only",
])
def test_the_counter_readers_give_nothing_where_nothing_is_read(windows, name,
                                                                window):
    obs = {"nothing": {}, "none": {"samples": None}, "empty": {"samples": []},
           "parent": {"samples": windows["parent"]},
           "stall-only": {"samples": [{"phase": "stall", "wall_ms": 130.0}]},
           }[window]
    assert metric(name)["read"](obs) is None


def test_the_report_leaves_them_out_on_a_parent_and_never_raises(windows):
    names = [(name, ENTRIES[name][0]) for name in ENTRIES]
    parent = {**traced(PARENT_SPANS), "samples": windows["parent"]}
    assert observe.report(names, "layer_metrics", [BENCH], parent) == {}
    change = {**traced(CHANGE_SPANS), "samples": windows["change"]}
    got = observe.report(names, "layer_metrics", [BENCH], change)
    assert set(got) == set(ENTRIES)
    assert {got[name]["unit"] for name in SPAN_READERS} == {"ms/s"}


# -- BENCHMARK.json ---------------------------------------------------------


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_benchmark_s_entry_is_the_reader_s_and_lists_the_six_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    unit, source, layer, moves = ENTRIES[name]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": moves,
        "workloads": [w["name"] for w in bench["workloads"]][:6],
    }
    meta = metric(name)
    assert {k: meta[k] for k in ("unit", "better", "source", "layer", "moves")} \
        == {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
    # every listed cell reports the end-to-end metric the reader moves
    moved = next(m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(entry["workloads"]) <= set(
        moved.get("workloads") or [w["name"] for w in bench["workloads"]])


def test_the_held_names_are_the_program_s():
    import importlib.util

    path = os.path.join(ROOT, "langstream_tpu", "serving", "flight.py")
    with open(path) as f:
        source = f.read()
    held = metric("idle_loop_lag_ms_s")
    module_spec = importlib.util.spec_from_file_location(
        "_lag", observe.find("layer_metrics", "idle_loop_lag_ms_s", [BENCH]))
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    assert held["unit"] == "ms/s"
    for name in module.HELD + module.WAITS:
        assert f'"{name}"' in source, name
    assert ('HELD_SPANS = ("ls.prefill.handoff", "ls.prefill.fetch", '
            '"ls.decode.fetch")') in source
