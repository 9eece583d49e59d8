"""The five readers of the device's own clock (``bench/lib/devclock.py``):
on hand-built flight samples with known answers, on the hand-written trace of
``fixtures/hosttrace.xplane.txt`` with the watcher's and the dispatch
thread's spans added, and on a parent commit's samples, which carry
``gap_ms`` / ``program_ms`` as bounds and no ``seen_by``."""

import json
import os

import pytest
from jax.profiler import ProfileData

from lib import devclock, hosttrace, observe, peaks, xplane

HERE = os.path.dirname(__file__)
ROOT = os.path.join(HERE, "..", "..")
BENCH = os.path.join(ROOT, "bench")
FIXTURE = os.path.join(HERE, "fixtures", "hosttrace.xplane.txt")
WINDOW = ["device_idle_window_share", "decode_dev_ms_step_window",
          "prefill_dev_ms_step_window", "prefill_window_mfu"]
NEW = WINDOW + ["device_clock_late_ms_p95"]
PEAKS = peaks.peaks_for("TPU v5 lite")
#: one prompt a prefill program, by the cells the window's share lists
PREFILL_CELLS = {
    "deepseekv2-longdoc-sat": "deepseek-v2-ep8",
    "trinitylarge-longctx-sat": "trinity-large-preview-ep8",
    "mellum2-codemix-sat": "mellum2-12b-a2.5b-8l",
    "evabyte-bytedoc-sat": "evabyte-6.5b-8l",
}


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


def sample(phase, seq, gap, program, by="watch", **kw):
    base = {"phase": phase, "dispatch": seq, "gap_ms": gap,
            "program_ms": program, "steps": 16 if phase == "decode" else 0}
    if by:
        base["seen_by"] = by
    return {**base, **kw}


#: a window of five dispatches and a stall; a verify step counts as the
#: device's time and as nobody's steps
SAMPLES = [
    sample("decode", 1, 0.0, 240.0),
    sample("prefill", 2, 1.5, 310.0, by="fetch", prompt_tokens=6000),
    sample("prefill", 3, 0.0, 190.0, prompt_tokens=4096),
    sample("decode", 4, 2.5, 256.0, by="fetch", steps=8),
    sample("verify", 5, 1.0, 4.0, steps=0),
    {"phase": "stall", "wall_ms": 3.0},
]


def parent(samples):
    """The same window as the parent commit wrote it: the two fields as the
    dispatch thread's bounds, no ``seen_by``."""
    return [{k: v for k, v in s.items() if k != "seen_by"} for s in samples]


# -- the four readers of the window's samples ------------------------------


def test_only_samples_the_clock_stamped_are_read():
    assert devclock.clocked({"samples": SAMPLES}) == SAMPLES[:5]
    assert devclock.clocked({"samples": SAMPLES}, "prefill") == SAMPLES[1:3]
    assert devclock.clocked({"samples": parent(SAMPLES)}) == []
    assert devclock.clocked({}) == [] and devclock.clocked({"samples": None}) == []


def test_the_window_s_idle_share_decode_step_and_prefill_a_step():
    obs = {"samples": SAMPLES}
    idle, busy = 5.0, 1000.0
    assert reader("device_idle_window_share")(obs) == pytest.approx(
        100.0 * idle / (idle + busy))
    assert reader("decode_dev_ms_step_window")(obs) == pytest.approx(
        (240.0 + 256.0) / 24)
    assert reader("prefill_dev_ms_step_window")(obs) == pytest.approx(
        (310.0 + 190.0) / 24)
    # by the tiling: decode + prefill + the rest + the gaps a step are the
    # stretch's wall a step
    assert (496.0 + 500.0 + 4.0 + 5.0) / 24 == pytest.approx(1005.0 / 24)


def test_a_window_without_prefills_waits_for_none():
    obs = {"samples": [SAMPLES[0], SAMPLES[3]]}
    assert reader("prefill_dev_ms_step_window")(obs) == 0.0
    assert reader("prefill_window_mfu")({**obs, "peaks": PEAKS}) is None


@pytest.mark.parametrize("cell", sorted(PREFILL_CELLS))
def test_the_window_s_prefill_share_is_the_family_s_own_arithmetic(cell):
    """The operations as that family's ``*_prefill_mfu`` reader calls them,
    over the samples' ``program_ms``."""
    from lib import roofline_eva, roofline_latent, roofline_swa, roofline_wf

    model = PREFILL_CELLS[cell]
    obs = {"samples": SAMPLES, "peaks": PEAKS, "serving": {"model": model}}
    prompts = [6000, 4096]
    if model.startswith("deepseek"):
        shape = roofline_latent.shape_of(obs)
        flops = roofline_latent.prefill_flops(
            shape, prompts, roofline_latent.mean_routed_pairs_token(shape))
    else:
        family = {"trinity": roofline_swa, "mellum2": roofline_wf,
                  "evabyte": roofline_eva}[model.split("-")[0]]
        flops = family.prefill_flops(family.shape_of(obs), prompts)
    share = reader("prefill_window_mfu")(obs)
    assert share == pytest.approx(100.0 * flops / 197e12 / 0.5)
    assert 0 < share < 100
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert cell in listed["prefill_window_mfu"]["workloads"]


@pytest.mark.parametrize("obs", [
    {"samples": SAMPLES, "peaks": PEAKS, "serving": {"model": "internlm2-1.8b"}},
    {"samples": SAMPLES, "peaks": PEAKS,
     "serving": {"model": "solar-open2-250b-ep8"}},
    {"samples": SAMPLES, "peaks": PEAKS},
    {"samples": SAMPLES, "peaks": None, "serving": {"model": "evabyte-6.5b-8l"}},
    {"samples": [s for s in SAMPLES if s.get("phase") != "prefill"],
     "peaks": PEAKS, "serving": {"model": "evabyte-6.5b-8l"}},
], ids=["dense", "delta", "no-serving", "no-peaks", "no-prefill"])
def test_a_family_without_prefill_flops_gives_no_share(obs):
    assert reader("prefill_window_mfu")(obs) is None


# -- the stamps beside the trace --------------------------------------------


def profile(watch=True, waits=True):
    """The fixture's trace (decode [0,4000) prefill [6000,8000) decode
    [10000,12000) split [13000,13500) decode [14500,15000), in ns) with a
    third thread, the watcher's, and the dispatch thread's waits:

      dev.watch       seq 5 [100,4300)   seq 6 [4300,8400)
                      seq 7 [8400,12050) seq 8 [12050,15100)
      ls.prefill.wait seq 6 [6100,8100)
      ls.decode.wait  seq 7 [11000,12500)  seq 8 [14600,15200)
    """
    with open(FIXTURE) as f:
        text = f.read()
    def event(meta, start, end, seq):
        return (f"    events {{ metadata_id: {meta} offset_ps: {start}000 "
                f"duration_ps: {end - start}000 stats {{ metadata_id: 1 "
                f"int64_value: {seq} }} }}\n")

    text = text.replace(
        '  stat_metadata { key: 1 value { id: 1 name: "seq" } }',
        '  event_metadata { key: 7 value { id: 7 name: "dev.watch" } }\n'
        '  event_metadata { key: 8 value { id: 8 name: "ls.decode.wait" } }\n'
        '  event_metadata { key: 9 value { id: 9 name: "ls.prefill.wait" } }\n'
        '  stat_metadata { key: 1 value { id: 1 name: "seq" } }')
    lines = ""
    if watch:
        lines += ('  lines {\n    id: 3\n    name: "python3"\n'
                  '    timestamp_ns: 1000\n'
                  + event(7, 100, 4300, 5) + event(7, 4300, 8400, 6)
                  + event(7, 8400, 12050, 7) + event(7, 12050, 15100, 8)
                  + "  }\n")
    if waits:
        lines += ('  lines {\n    id: 4\n    name: "python3"\n'
                  '    timestamp_ns: 1000\n'
                  + event(9, 6100, 8100, 6) + event(8, 11000, 12500, 7)
                  + event(8, 14600, 15200, 8) + "  }\n")
    end = text.rindex("}")
    return ProfileData.from_text_proto(text[:end] + lines + text[end:])


#: the flight samples of the traced dispatches, the gaps and programs as the
#: clock took them (the key split between 7 and 8 is no dispatch of its own)
TRACED = [
    sample("decode", 5, 0.0, 4.3e-3),
    sample("prefill", 6, 1.7e-3, 2.1e-3, by="fetch"),
    sample("decode", 7, 1.9e-3, 2.05e-3),
    sample("decode", 8, 2.45e-3, 0.6e-3, by="fetch"),
]


def test_each_stamp_is_paired_with_the_end_of_its_own_program_s_run():
    paired = devclock.pair(profile(), TRACED, 0.0)
    # 5: the watcher's, 300 ns after its run; 6: the fetch's (8100), the
    # watcher's later one lost; 7: the watcher's, 50 ns; 8: stamped by the
    # dispatch thread inside another program's wait, so the earlier of the
    # two spans' ends (the watcher's, 15100) stands for it
    assert paired["paired_by"] == "order"
    assert paired["late_ms"] == pytest.approx([300e-6, 100e-6, 50e-6, 100e-6])
    assert paired["late_ms_by"] == {
        "watch": pytest.approx([300e-6, 50e-6]),
        "fetch": pytest.approx([100e-6, 100e-6])}
    assert paired["seen_by"] == {"watch": 2, "fetch": 2}
    # the stretch from 5's stamp (4300) to 8's (15100): the clock's account
    # of 6, 7 and 8, and the trace's gaps clipped to it (1700 + 2000 + 1000
    # + 1000 ns between programs, none inside one)
    assert paired["stretch_ms"] == pytest.approx(10800e-6)
    assert (paired["dispatches"], paired["missing"]) == (3, 0)
    assert paired["clock_idle_ms"] == pytest.approx(6.05e-3)
    assert paired["clock_busy_ms"] == pytest.approx(4.75e-3)
    assert paired["clock_idle_ms"] + paired["clock_busy_ms"] == pytest.approx(
        paired["stretch_ms"])
    assert paired["trace_idle_between_ms"] == pytest.approx(5700e-6)
    assert paired["trace_idle_inside_ms"] == 0.0


@pytest.mark.parametrize("skew, used, late", [
    # the device's clock reads 30 ns early: every run ended that much later
    (30.0, 30.0, [270e-6, 70e-6, 20e-6, 70e-6]),
    # no completion is seen before it happened: a disagreement of 200 ns
    # would put 7's stamp (50 ns after its run) before its run's end, so the
    # clocks disagree by 50 ns at most, and that is taken
    (200.0, 50.0, [250e-6, 50e-6, 0.0, 50e-6]),
    (0.0, 0.0, [300e-6, 100e-6, 50e-6, 100e-6]),
], ids=["moved", "capped-by-causality", "agree"])
def test_the_clocks_disagreement_is_hosttrace_s_or_what_causality_allows(
        skew, used, late):
    paired = devclock.pair(profile(), TRACED, skew)
    assert paired["skew_host_ms"] == pytest.approx(skew / 1e6)
    assert paired["skew_used_ms"] == pytest.approx(used / 1e6)
    assert paired["late_ms"] == pytest.approx(late)
    # the device's gaps are moved by as much: the first one, [4000,6000),
    # loses to the stretch's start (4300) what the move leaves of it
    assert paired["trace_idle_between_ms"] == pytest.approx(
        (5700 + used) / 1e6)


def test_a_stamp_s_own_run_is_found_by_order_not_by_the_clocks():
    """Runs of one phase back to back, and a device timeline 4 us early,
    more than a run is long: by time every stamp would take a later run's
    end; by order each takes its own."""
    runs = [(0.0, 1000.0, "prefill"), (1000.0, 2000.0, "prefill"),
            (2000.0, 3000.0, "decode"), (3000.0, 4000.0, "prefill")]
    stamped = {11: 5010.0, 12: 6030.0, 13: 7020.0}      # 4 us + 10 / 30 / 20
    phases = {11: "prefill", 12: "prefill", 13: "decode", 14: "prefill"}
    slack = {"SKEW_MAX_NS": devclock.SKEW_MAX_NS, "LATE_NS": devclock.LATE_NS}
    assert devclock.align(stamped, phases, runs) == {
        11: 1000.0, 12: 2000.0, 13: 3000.0}
    # a stamp with no run (the trace began after its run had): the others
    # keep theirs
    assert devclock.align({10: 4000.0, **stamped}, phases, runs) == {
        11: 1000.0, 12: 2000.0, 13: 3000.0}
    # ... but not when half the stamps would go without one
    assert devclock.align({10: 4000.0, **stamped}, phases, runs[1:]) is None
    # a chunk left pending, closed inside a prefill's wait and fetched 80 ms
    # after it ended: its own wait's end is no stamp, and it stays unpaired
    pending = {10: 8.0e7, 11: 5010.0 + 2000, 12: 6030.0 + 2000, 13: 9020.0}
    assert devclock.align(
        pending, {10: "decode", 11: "prefill", 12: "prefill", 13: "decode",
                  14: "prefill"},
        [(0.0, 2000.0, "decode")] + [(a + 2000, b + 2000, p)
                                     for a, b, p in runs]) == {
        11: 3000.0, 12: 4000.0, 13: 5000.0}
    # phases that fit nowhere, nothing to align: no order
    assert devclock.align(stamped, {**phases, 12: "decode"}, runs) is None
    assert devclock.align({}, phases, runs) is None
    assert devclock.align(stamped, phases, []) is None
    assert slack == {"SKEW_MAX_NS": 5e6, "LATE_NS": 50e6}


def test_where_no_order_fits_a_stamp_takes_the_run_that_ended_last_before_it():
    # 5 a decode chunk and 7 a prefill: no run of the trace is a prefill two
    # after a decode chunk, so each stamp takes, on the moved timeline, the
    # run of its phase that ended last before it
    far = [sample("decode", 5, 0.0, 1.0), sample("prefill", 7, 0.0, 1.0)]
    paired = devclock.pair(profile(), far, 0.0, slack_ns=0.0)
    assert paired["paired_by"] == "time"
    assert paired["late_ms"] == pytest.approx([300e-6, 4050e-6])
    assert paired["skew_used_ms"] == pytest.approx(0.0)
    # every run 60 ms before its stamp: too long ago to be its own
    assert devclock.pair(profile(), far, 60e6) is None
    # one stamp: its run by order
    alone = devclock.pair(profile(), far[1:], 0.0)
    assert alone["paired_by"] == "order"
    assert alone["late_ms"] == pytest.approx([4050e-6])
    assert devclock.pair(profile(watch=False, waits=False), TRACED, 0.0) is None


def test_the_reader_reads_the_run_s_trace_once_and_says_what_it_read(
        monkeypatch, capsys):
    loaded = []
    monkeypatch.setattr(devclock, "SKEW_NS", 0.0)   # the fixture is 15 us long
    monkeypatch.setattr(hosttrace, "find_trace", lambda root=None: "a.xplane.pb")
    monkeypatch.setattr(xplane, "load",
                        lambda path: loaded.append(path) or profile())
    obs = {"trace": {"window_s": 15e-6}, "samples": TRACED,
           "hosttrace": {"clock_skew_ns": 0.0, "spans": []}}
    assert reader("device_clock_late_ms_p95")(obs) == pytest.approx(300e-6)
    assert reader("device_clock_late_ms_p95")(obs) == pytest.approx(300e-6)
    assert loaded == ["a.xplane.pb"]
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("[bench] the device's clock beside the trace: ")]
    assert len(said) == 1
    note = json.loads(said[0].split(": ", 1)[1])
    assert note["stamps"] == 4 and note["late_ms_max"] == pytest.approx(300e-6)
    assert note["trace_idle_between_ms"] == pytest.approx(5700e-6)


# -- a parent commit, an untraced run ----------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_gives_nothing(name, monkeypatch):
    """No samples; a parent's samples (the bounds, no ``seen_by``) beside a
    trace without the watcher's span; an untraced run for the error bar:
    None, never an exception."""
    monkeypatch.setattr(hosttrace, "find_trace", lambda root=None: "a.xplane.pb")
    monkeypatch.setattr(xplane, "load", lambda path: profile(watch=False))
    read = reader(name)
    base = {"peaks": PEAKS, "serving": {"model": "evabyte-6.5b-8l"}}
    assert read({**base, "samples": [], "trace": None}) is None
    assert read({**base, "trace": None}) is None
    assert read({**base, "samples": parent(SAMPLES + TRACED),
                 "trace": {"window_s": 15e-6},
                 "hosttrace": {"clock_skew_ns": 0.0, "spans": []}}) is None
    if name == "device_clock_late_ms_p95":
        assert read({**base, "samples": TRACED, "trace": None}) is None


def test_the_report_leaves_them_out_on_a_parent_and_has_them_on_the_change():
    names = [(name, "x") for name in WINDOW]
    base = {"peaks": PEAKS, "serving": {"model": "evabyte-6.5b-8l"},
            "trace": None}
    on_parent = observe.report(names, "layer_metrics", [BENCH],
                               {**base, "samples": parent(SAMPLES)})
    assert on_parent == {}
    on_change = observe.report(names, "layer_metrics", [BENCH],
                               {**base, "samples": SAMPLES})
    assert sorted(on_change) == sorted(WINDOW)
    assert all(v["value"] >= 0 for v in on_change.values())


# -- the benchmark's entries --------------------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_the_benchmark_s_entry_is_the_reader_s_own(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    meta = observe.load_metric(observe.find("layer_metrics", name, [BENCH]))
    assert {k: entry[k] for k in ("unit", "better", "layer", "moves",
                                  "source")} == {
        k: meta[k] for k in ("unit", "better", "layer", "moves", "source")}
    cells = [w["name"] for w in bench["workloads"]]
    if name == "prefill_window_mfu":
        assert entry["workloads"] == list(PREFILL_CELLS)
    else:       # every cell, those later PRs add too
        assert "workloads" not in entry
    assert set(entry.get("workloads", cells)) <= set(cells)
    # appended: nothing that was there moved
    assert [m["name"] for m in bench["per_layer"]][-5:] == NEW


# -- the watcher's span takes no idle time -----------------------------------


def test_the_watcher_s_span_is_not_a_host_span_and_attributes_nothing():
    plain = ProfileData.from_text_proto(open(FIXTURE).read())
    watched = profile(waits=False)
    assert "dev.watch" in {n for p in watched.planes for line in p.lines
                           for _, _, n, _ in xplane._events(line)}
    spans = hosttrace.host_spans(watched)
    assert spans == hosttrace.host_spans(plain)
    assert all(s["name"].startswith("ls.") for s in spans)
    assert not devclock.WATCH_SPAN.startswith(hosttrace.SPAN_PREFIX)
    # so the idle_* readers of the fixture read what they read without it
    a, b = hosttrace.reduce(plain), hosttrace.reduce(watched)
    assert a["idle"] == b["idle"] and a["clock_skew_ns"] == b["clock_skew_ns"]
    for name in ("idle_prefill_host_ms_s", "idle_decode_host_ms_s",
                 "idle_loop_lag_ms_s", "idle_attributed_share"):
        obs = [{"trace": xplane.reduce(p, window_s=15e-6), "hosttrace": r,
                "llama": {"layers": 1}, "samples": []}
               for p, r in ((plain, a), (watched, b))]
        assert reader(name)(obs[0]) == reader(name)(obs[1])


def test_the_program_s_span_name_is_the_one_the_benchmark_reads():
    from langstream_tpu.serving import flight

    assert flight.WATCH_SPAN == devclock.WATCH_SPAN == "dev.watch"
    assert flight.WATCH_SPAN not in flight.SPANS
    assert set(devclock.WAIT_SPANS) <= set(flight.SPANS)
