"""``tools/ops_of_shape.py``: the reader that shows "no copy of the pool",
on a handwritten module and on the benchmark's handwritten trace."""

import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

HLO = """\
HloModule jit__prefill, is_scheduled=true, entry_computation_layout={(bf16[24,901,64,1024]{3,2,1,0:T(8,128)(2,1)})->bf16[24,901,64,1024]{3,2,1,0:T(8,128)(2,1)}}

%fused_computation.4 (param_0.1: bf16[1383936,1024], param_1.2: s32[4096], param_2.3: bf16[4096,1024]) -> bf16[1383936,1024] {
  %param_0.1 = bf16[1383936,1024]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.1 = bf16[1383936,1024]{1,0:T(8,128)(2,1)} scatter(%param_0.1, %param_1.2, %param_2.3), update_window_dims={1}
}

ENTRY %main.5 (cache_k.1: bf16[24,901,64,1024]) -> (s32[8], bf16[24,901,64,1024]) {
  %cache_k.1 = bf16[24,901,64,1024]{3,2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="cache_k"}
  %copy.36 = bf16[24,901,64,1024]{3,1,2,0:T(8,128)(2,1)} copy(%cache_k.1)
  %bitcast.7 = bf16[1383936,1024]{1,0:T(8,128)(2,1)} bitcast(%cache_k.1)
  %fusion.4 = bf16[1383936,1024]{1,0:T(8,128)(2,1)} fusion(%bitcast.7, %idx, %rows), kind=kCustom, calls=%fused_computation.4
  %fusion.9 = (s32[8]{0:T(128)}, bf16[24,901,64,1024]{3,2,1,0:T(8,128)(2,1)}) fusion(%fusion.4), kind=kLoop, calls=%fused_computation.9
  %other.2 = bf16[24,901,64,128]{3,2,1,0} copy(%x)
  ROOT %tuple.3 = (s32[8]{0:T(128)}, bf16[24,901,64,1024]{3,2,1,0:T(8,128)(2,1)}) tuple(%tokens, %bitcast.8)
}
"""


def load_tool():
    spec = importlib.util.spec_from_file_location(
        "ops_of_shape", ROOT / "tools" / "ops_of_shape.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tool():
    return load_tool()


def test_instructions_are_found_by_any_result_in_any_computation(tool):
    ops = tool.hlo_ops_of_shape(
        HLO, ["bf16[24,901,64,1024]", "bf16[1383936, 1024]"])
    assert ops == [
        ("param_0.1", "parameter", "bf16[1383936,1024]"),
        ("scatter.1", "scatter", "bf16[1383936,1024]"),
        ("cache_k.1", "parameter", "bf16[24,901,64,1024]"),
        ("copy.36", "copy", "bf16[24,901,64,1024]"),
        ("bitcast.7", "bitcast", "bf16[1383936,1024]"),
        ("fusion.4", "fusion", "bf16[1383936,1024]"),
        ("fusion.9", "fusion", "bf16[24,901,64,1024]"),    # a tuple's element
        ("tuple.3", "tuple", "bf16[24,901,64,1024]"),
    ]
    # what names a value moves nothing: the copy and the scatter are left
    assert [name for name, _, _ in tool.moved(ops)] == [
        "scatter.1", "copy.36", "fusion.4", "fusion.9"]
    assert tool.hlo_ops_of_shape(HLO, ["s8[32,1228,64,1024]"]) == []


def test_a_trace_s_ops_are_found_by_the_shape_in_their_name(tool):
    from jax.profiler import ProfileData

    _, xplane = tool._bench_lib()
    fixture = ROOT / "tests" / "bench" / "fixtures" / "mini.xplane.txt"
    text = "\n".join(line for line in fixture.read_text().splitlines()
                     if not line.startswith("#"))
    reduced = xplane.reduce(ProfileData.from_text_proto(text))
    (op,) = tool.trace_ops_of_shape(reduced, ["bf16[64,4096]"])
    assert op["name"] == "fusion.1_bf16_64_4096_"
    assert (op["calls"], op["program"]) == (4, "jit__decode_chunk")
    assert op["total_s"] == pytest.approx(2.5e-6)
    assert tool.trace_ops_of_shape(reduced, ["bf16[24,901,64,1024]"]) == []


def test_the_command_prints_each_op_and_the_count(tool, tmp_path, capsys):
    path = tmp_path / "prefill.txt"
    path.write_text(HLO)
    assert tool.main(["--shape", "bf16[24,901,64,1024]", "--hlo", str(path)]) == 0
    out = capsys.readouterr().out.splitlines()
    assert f"{path}: copy.36 copy bf16[24,901,64,1024]" in out
    assert f"{path}: cache_k.1 parameter bf16[24,901,64,1024] (free)" in out
    assert out[-1].startswith("2 ops of shape bf16[24,901,64,1024]")
