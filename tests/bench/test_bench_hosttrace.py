"""``bench/lib/hosttrace.py`` on a hand-written trace with known answers
(``fixtures/hosttrace.xplane.txt``: four idle gaps of one device, five host
spans on two threads), on a hand-encoded ``.xplane.pb`` holding a program's
HLO, and on nothing; and the seven per-layer readers built on it."""

import json
import os

import pytest
from jax.profiler import ProfileData

from lib import hosttrace, observe, xplane

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
FIXTURE = os.path.join(HERE, "fixtures", "hosttrace.xplane.txt")
NEW = ["kv_read_dev_ms_step", "idle_attributed_share", "idle_admit_ms_s",
       "idle_prefill_host_ms_s", "idle_decode_host_ms_s",
       "prefill_interleave_ms_step", "occupancy_dispatch_mean"]
# what the decode program's HLO says of the fixture's operations
SCOPES = {"jit__decode_chunk(111)": {
    "fusion.1": "kv_read", "paged_read.7": "kv_read", "copy.4": "kv_read",
    "fusion.5": "ffn"}}


def profile(text=None):
    if text is None:
        with open(FIXTURE) as f:
            text = f.read()
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def reduced():
    return hosttrace.reduce(profile(), SCOPES)


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


# -- (a) the spans -------------------------------------------------------


def test_spans_of_every_host_thread_and_nothing_else(reduced):
    spans = reduced["spans"]
    assert [s["name"] for s in spans] == [
        "ls.prefill.pack", "ls.prefill.emit", "ls.decode.prepare",
        "ls.decode.fetch", "ls.decode.dispatch"]   # by start; no $python_fn
    assert {s["thread"] for s in spans} == {0, 1}
    dispatch = spans[-1]
    assert dispatch["meta"] == {"seq": 7, "program": "decode:w128:k4:greedy"}
    assert (dispatch["start_ns"], dispatch["end_ns"]) == (13200.0, 13600.0)


# -- (b) the idle gaps, by host span -------------------------------------


@pytest.mark.parametrize("gap, expected", [
    # wholly under one span
    ("jit__decode_chunk_-_jit__prefill", {"ls.prefill.pack": 2000}),
    # split between two, with a stretch under none between them
    ("jit__prefill_-_jit__decode_chunk",
     {"ls.prefill.emit": 800, "none": 200, "ls.decode.prepare": 1000}),
    # nested, on two threads: the innermost wins
    ("jit__decode_chunk_-_jit__threefry_split",
     {"ls.decode.dispatch": 400, "ls.decode.fetch": 600}),
    # under no span at all
    ("jit__threefry_split_-_jit__decode_chunk", {"none": 1000}),
])
def test_each_gap_is_split_among_the_innermost_spans(reduced, gap, expected):
    row = reduced["idle"]["by_gap"][gap]
    assert row["count"] == 1
    assert {k: round(v * 1e9) for k, v in row["by_span"].items()} == expected
    assert round(row["total_s"] * 1e9) == sum(expected.values())


def test_idle_totals_and_what_ran_between_spans(reduced):
    idle = reduced["idle"]
    assert round(idle["idle_s"] * 1e9) == 6000
    assert round(idle["attributed_s"] * 1e9) == 4800
    assert {k: round(v * 1e9) for k, v in idle["by_span"].items()} == {
        "ls.prefill.pack": 2000, "ls.prefill.emit": 800,
        "ls.decode.prepare": 1000, "ls.decode.fetch": 600,
        "ls.decode.dispatch": 400, "none": 1200}
    # uncovered time is named by the spans around it
    assert {k: round(v * 1e9) for k, v in idle["none_between"].items()} == {
        "ls.prefill.emit_-_ls.decode.prepare": 200,
        "ls.decode.fetch_-_none": 1000}


@pytest.mark.parametrize("spans, expected", [
    ([], [(0, 10, "none")]),
    ([("a", 0, 10)], [(0, 10, "a")]),
    ([("a", -5, 4), ("b", 6, 20)],
     [(0, 4, "a"), (4, 6, "none"), (6, 10, "b")]),
    # the one that started last is the innermost; on a tie the shorter
    ([("outer", 0, 10), ("inner", 2, 5)],
     [(0, 2, "outer"), (2, 5, "inner"), (5, 10, "outer")]),
    ([("long", 0, 10), ("short", 0, 3)], [(0, 3, "short"), (3, 10, "long")]),
    # spans that only touch the gap's edges cover nothing of it
    ([("before", -5, 0), ("after", 10, 12)], [(0, 10, "none")]),
])
def test_split_gap(spans, expected):
    spans = [{"name": n, "start_ns": a, "end_ns": b} for n, a, b in spans]
    assert hosttrace.split_gap(0, 10, spans) == expected


@pytest.mark.parametrize("program_start, span_starts, skew", [
    # the split program starts inside the span that issued it: clocks agree
    (13000, {"ls.prefill.dispatch": [12900]}, 0),
    # it "starts" 1000 ns before its span: the device's clock reads early
    (13000, {"ls.prefill.dispatch": [14000]}, 1000),
    (13000, {"ls.decode.prepare": [14000], "ls.prefill.dispatch": [900]}, 1000),
    # a span that issues no key split is no anchor; nor is one far away
    (13000, {"ls.decode.dispatch": [14000]}, 0),
    (13000, {"ls.prefill.dispatch": [13000 + 6e6]}, 0),
])
def test_clock_skew_from_the_key_split(program_start, span_starts, skew):
    gaps = [(12000, program_start, "jit__decode_chunk_-_jit__threefry_split"),
            (13500, 14500, "jit__threefry_split_-_jit__decode_chunk")]
    spans = [{"name": name, "start_ns": t, "end_ns": t + 500}
             for name, starts in span_starts.items() for t in starts]
    assert hosttrace.clock_skew_ns(gaps, spans) == skew


def test_the_fixture_s_clocks_agree(reduced):
    assert reduced["clock_skew_ns"] == 0


# -- (c) device time by scope --------------------------------------------


def test_decode_time_by_scope_and_the_rest_by_operation(reduced):
    scopes = reduced["scopes"]
    # the three decode runs; the %while container and the other programs'
    # operations are left out
    assert round(scopes["total_s"] * 1e9) == 6500
    assert {k: round(v * 1e9) for k, v in scopes["by_scope"].items()} == {
        "kv_read": 5000, "ffn": 1000}
    assert {k: round(v * 1e9) for k, v in scopes["unscoped"].items()} == {
        "copy.6_bf16_24_901_64_1024_": 500}


def test_without_the_programs_hlo_everything_is_under_no_scope():
    scopes = hosttrace.reduce(profile())["scopes"]
    assert scopes["by_scope"] == {} and round(scopes["total_s"] * 1e9) == 6500


@pytest.mark.parametrize("path, scope", [
    ("jit(_decode_chunk)/while/body/closed_call/while/body/closed_call/"
     "kv_read/paged_read/pallas_call", "kv_read"),
    ("jit(_decode_chunk)/while/body/closed_call/sample/jit(_where)/select_n",
     "sample"),
    ("jit(_prefill)/while/body/closed_call/flash/flash_prefill/pallas_call",
     "flash"),
    ("jit(_decode_chunk)/while/body/closed_call/while/body/squeeze", None),
    ("", None), (None, None),
])
def test_scope_of_a_name_stack(path, scope):
    assert hosttrace.scope_of(path) == scope


# -- the file itself: the programs' HLO ----------------------------------


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(ident, name, op_name="", operands=(), packed=False):
    out = field(1, name)
    if op_name:
        out += field(7, field(2, op_name))
    out += field(35, ident)
    if packed:
        out += field(36, b"".join(varint(o) for o in operands))
    else:
        out += b"".join(field(36, o) for o in operands)
    return field(2, out)


def xspace_with_hlo(packed=False):
    stack = "jit(_decode_chunk)/while/body/closed_call/while/body/"
    body = (
        instruction(1, "fusion.1", stack + "closed_call/kv_read/dot_general",
                    [6], packed)
        + instruction(2, "paged_read.7",
                      stack + "closed_call/kv_read/paged_read/pallas_call",
                      [3, 4], packed)
        # the layer scan's slice of the stacked pool: no scope of its own,
        # read by a copy the compiler put in, read by the kernel
        + instruction(3, "dynamic-slice_bitcast_fusion.10", stack + "squeeze")
        + instruction(4, "copy.4", "", [3], packed)
        + instruction(5, "fusion.5", stack + "closed_call/ffn/dot_general",
                      [6], packed)
        # read by two scopes: stays under none
        + instruction(6, "copy.6")
    )
    hlo = field(1, field(3, body))
    program = (field(1, 111) + field(2, "jit__decode_chunk(111)")
               + field(5, field(1, 1) + field(6, hlo)))
    return (
        field(1, field(2, "/device:TPU:0")
              + field(4, field(1, 9) + field(2, field(2, "%fusion.1 = x"))))
        + field(1, field(2, "/host:metadata")
                + field(4, field(1, 111) + field(2, program)))
    )


@pytest.mark.parametrize("packed", [False, True])
def test_scopes_are_read_from_the_hlo_the_trace_file_holds(tmp_path, packed):
    path = tmp_path / "vm.xplane.pb"
    path.write_bytes(xspace_with_hlo(packed))
    assert hosttrace.op_scopes(str(path)) == {"jit__decode_chunk(111)": {
        "fusion.1": "kv_read", "paged_read.7": "kv_read", "fusion.5": "ffn",
        # the unscoped operations the kernel reads take its scope, through
        # the chain; the one two scopes read keeps none
        "copy.4": "kv_read", "dynamic-slice_bitcast_fusion.10": "kv_read",
    }}


def test_a_file_without_the_hlo_plane_gives_no_scopes(tmp_path):
    path = tmp_path / "vm.xplane.pb"
    path.write_bytes(field(1, field(2, "/host:CPU")))
    assert hosttrace.op_scopes(str(path)) == {}


def test_the_newest_trace_under_a_directory_is_found(tmp_path):
    assert hosttrace.find_trace(str(tmp_path)) is None
    for stamp, when in (("2026_01_01", 100), ("2026_01_02", 200)):
        folder = tmp_path / "plugins" / "profile" / stamp
        folder.mkdir(parents=True)
        (folder / "vm.xplane.pb").write_bytes(b"")
        os.utime(folder / "vm.xplane.pb", (when, when))
    assert hosttrace.find_trace(str(tmp_path)).endswith(
        os.path.join("2026_01_02", "vm.xplane.pb"))


def test_as_a_script_it_prints_the_two_tables(tmp_path, capsys):
    assert hosttrace.main([]) == 2
    assert hosttrace.main([str(tmp_path)]) == 1
    text = hosttrace.tables(hosttrace.reduce(profile(), SCOPES))
    assert "idle by host span (ms):" in text
    assert "ls.prefill.pack" in text and "under a scope 92.31%" in text
    assert "(no scope) copy.6_bf16_24_901_64_1024_" in text
    assert "ls.decode.fetch_-_none" in text


# -- the readers ---------------------------------------------------------


def obs(reduced=None, **kw):
    """What a traced run of the fixture's trace would have observed: the
    reduction of ``lib/xplane.py`` (3 decode runs of 2 layers: 2, 1 and 0
    whole steps), this module's, and flight samples of the window."""
    trace = xplane.reduce(profile(), window_s=15e-6)
    base = {
        "trace": trace, "llama": {"layers": 1}, "hosttrace": reduced,
        "samples": [
            {"phase": "decode", "dispatch": 7, "steps": 32,
             "active_at_dispatch": 64, "occupancy": 50},
            {"phase": "decode", "dispatch": 9, "steps": 8,
             "active_at_dispatch": 24, "occupancy": 24},
            {"phase": "prefill", "dispatch": 8, "steps": 0,
             "active_at_dispatch": 60, "occupancy": 64},
            {"phase": "stall"},
        ],
    }
    return {**base, **kw}


def test_the_readers_on_the_fixture(reduced):
    o = obs(reduced)
    # decode steps as decode_dev_ms_step counts them: the most frequent op
    # of a run over the layers: 1 + 1 + 1 runs of one layer
    steps = hosttrace.decode_steps(o)
    assert steps == 3
    assert reader("kv_read_dev_ms_step")(o) == pytest.approx(1e3 * 5000e-9 / 3)
    assert reader("idle_attributed_share")(o) == pytest.approx(80.0)
    per_s = 1e3 / 15e-6   # milliseconds of idle per traced second
    assert reader("idle_admit_ms_s")(o) == pytest.approx(0.0)
    assert reader("idle_prefill_host_ms_s")(o) == pytest.approx(2800e-9 * per_s)
    assert reader("idle_decode_host_ms_s")(o) == pytest.approx(2000e-9 * per_s)
    # prefill 2000 ns and threefry_split 500 ns over 3 steps
    assert reader("prefill_interleave_ms_step")(o) == pytest.approx(
        1e3 * 2500e-9 / 3)
    # weighted by steps: (64 x 32 + 24 x 8) / 40
    assert reader("occupancy_dispatch_mean")(o) == pytest.approx(56.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_gives_nothing(name):
    """An untraced run; a traced run of a program that opens no spans, names
    no scopes and stamps no dispatch (the parent commit): None, never an
    exception. ``prefill_interleave_ms_step`` reads the program line the
    parent has too."""
    assert reader(name)({"trace": None, "samples": [], "llama": {"layers": 1}}) is None
    with open(FIXTURE) as f:
        text = f.read()
    bare = text[:text.index('planes {\n  id: 2')]      # no host plane at all
    parent = obs(hosttrace.reduce(profile(bare)),
                 samples=[{"phase": "decode", "occupancy": 50}])
    value = reader(name)(parent)
    if name == "prefill_interleave_ms_step":
        assert value == pytest.approx(1e3 * 2500e-9 / 3)
    else:
        assert value is None


def test_an_empty_host_plane_gives_no_span_and_no_idle_metric():
    with open(FIXTURE) as f:
        text = f.read()
    empty = text[:text.index('planes {\n  id: 2')] + \
        'planes { id: 2 name: "/host:CPU" }\n'
    reduced = hosttrace.reduce(profile(empty), SCOPES)
    assert reduced["spans"] == []
    assert round(reduced["idle"]["idle_s"] * 1e9) == 6000   # all under none
    assert reduced["idle"]["attributed_s"] == 0.0
    o = obs(reduced)
    for name in ("idle_attributed_share", "idle_admit_ms_s",
                 "idle_prefill_host_ms_s", "idle_decode_host_ms_s"):
        assert reader(name)(o) is None
    assert reader("kv_read_dev_ms_step")(o) is not None   # scopes are there


def test_the_reduction_is_made_once_a_run(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(hosttrace, "find_trace",
                        lambda root=None: calls.append(1))
    o = {"trace": {"window_s": 4.0}}
    assert hosttrace.of(o) is None and hosttrace.of(o) is None
    assert calls == [1] and o["hosttrace"] is None


def test_the_benchmark_lists_the_seven_with_their_cells():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"]][-7:] == NEW
    for name in NEW:
        wanted = cells[:2] if name == "idle_attributed_share" else cells
        assert listed[name]["workloads"] == wanted, name
