"""What ``chip_smoke.py`` and the scripts around it must hold WITHOUT a chip:
the smoke refuses a machine that has none (naming what it saw), the compile
cache goes where it is placed, the bench parent stays off JAX, an unknown
TPU has no peaks, and a cache that cannot fit is refused in words."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(code_or_args, env_overrides=None, cwd=REPO, **kwargs):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    for key, value in (env_overrides or {}).items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, *code_or_args], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=180, **kwargs,
    )


def test_chip_smoke_fails_without_a_chip_and_names_the_platform():
    proc = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode not in (0, None)
    assert "'cpu'" in proc.stderr
    # no result: nothing on stdout parses as the ok line
    assert '"ok"' not in proc.stdout


def test_chip_smoke_fails_when_jax_itself_finds_only_the_cpu():
    """JAX_PLATFORMS unset: the environment does not refuse, JAX decides —
    and on a machine without a chip its first device is the CPU."""
    proc = _run(["chip_smoke.py"], {"JAX_PLATFORMS": None})
    assert proc.returncode not in (0, None)
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    script = tmp_path / "chip_smoke.py"
    script.write_text((REPO / "chip_smoke.py").read_text())
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=tmp_path, capture_output=True,
        text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert proc.returncode not in (0, None)
    assert '"ok"' not in proc.stdout


_COMPILE = (
    "from langstream_tpu.compile_cache import configure_compile_cache\n"
    "print(configure_compile_cache())\n"
    "import jax, jax.numpy as jnp\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()\n"
)


def test_compile_cache_honours_the_variable(tmp_path):
    """Placed from outside: files land there, none under the checkout's
    default directory, and no other path is set in code."""
    placed = tmp_path / "placed"
    default = REPO / ".jax_cache"
    before = set(default.iterdir()) if default.is_dir() else set()
    proc = _run(["-c", _COMPILE], {
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(placed),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(placed), str(placed)]
    wrote = {entry.name for entry in placed.iterdir()}
    assert wrote
    # only what the child wrote is looked at: the other workers of this
    # run compile into the default directory all the while (conftest.py),
    # and an entry's name is its program's key
    after = set(default.iterdir()) if default.is_dir() else set()
    assert not wrote & {entry.name for entry in after - before}


def test_compile_cache_default_is_one_fixed_path():
    from langstream_tpu import compile_cache

    assert compile_cache.DEFAULT_DIR == str(REPO / ".jax_cache")
    proc = _run(["-c", _COMPILE.split("jax.jit")[0]], {
        "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": None,
    })
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(REPO / ".jax_cache")] * 2


def test_compile_cache_after_jax_import_still_takes_effect():
    code = (
        "import jax\n"
        "from langstream_tpu.compile_cache import configure_compile_cache\n"
        "configure_compile_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    proc = _run(["-c", code], {
        "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": None,
    })
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(REPO / ".jax_cache")]


def test_bench_parent_stays_off_jax():
    """One process per chip: a parent that had touched JAX would hold the
    chip and every phase child would fail or hang."""
    proc = _run(
        ["-c", "import sys, bench; print('jax' in sys.modules)"],
        {"BENCH_PHASE": None},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"


def test_unknown_tpu_device_kind_raises(monkeypatch):
    import jax

    from langstream_tpu.serving import profiling

    class _Device:
        platform = "tpu"
        device_kind = "TPU v99 imaginary"

    monkeypatch.setattr(jax, "local_devices", lambda: [_Device()])
    with pytest.raises(profiling.UnknownDeviceError, match="TPU v99 imaginary"):
        profiling.device_peaks()
    _Device.device_kind = "TPU v5 lite"
    kind, peaks = profiling.device_peaks()
    assert kind == "TPU v5 lite" and peaks["hbm_gbps"] == 819.0 and peaks["source"]
    # off-TPU there is no roof, and not v5e's
    _Device.platform, _Device.device_kind = "cpu", "cpu"
    assert profiling.device_peaks() == ("cpu", None)


def test_engine_refuses_a_cache_that_cannot_fit(monkeypatch):
    import langstream_tpu.serving.engine as engine_mod
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    monkeypatch.setattr(engine_mod, "detect_hbm_bytes", lambda: 300_000)
    with pytest.raises(ValueError, match="does not fit the device.*bytes_limit"):
        TpuServingEngine(ServingConfig(model="tiny", slots=64, max_seq_len=512))
    # the limit the device reports is the only judge: a roomy one passes
    monkeypatch.setattr(engine_mod, "detect_hbm_bytes", lambda: 1 << 30)
    TpuServingEngine(ServingConfig(model="tiny", slots=4, max_seq_len=128))


def test_selected_kernel_is_never_substituted():
    """The model functions run the kernel they are handed or raise; the
    engine resolves the selection once and refuses what cannot be built."""
    import jax
    import jax.numpy as jnp

    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.models.llama_paged import llama_prefill_continue_paged
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    eng = TpuServingEngine(ServingConfig(
        model="tiny", max_seq_len=128, kv_layout="paged", kv_quantize="int8",
        paged_kernel="pallas-interpret",
    ))
    assert eng.paged_read_kernel == "pallas-interpret"
    assert eng.continuation_read_kernel == "xla"  # by selection, at init
    with pytest.raises(ValueError, match="cannot read an int8 pool"):
        llama_prefill_continue_paged(
            LlamaConfig.tiny(), eng.params, jnp.zeros((1, 8), jnp.int32),
            jnp.array([0]), jnp.array([8]), eng.cache_k, eng.cache_v,
            jnp.zeros((1, 2), jnp.int32), num_read_blocks=1,
            kernel="pallas-interpret",
        )
    with pytest.raises(ValueError, match="under a mesh"):
        TpuServingEngine(ServingConfig(
            model="tiny", max_seq_len=128, kv_layout="paged",
            kv_quantize="int8", paged_kernel="pallas-interpret",
            mesh=(("tp", 2),),
        ))
    assert jax.default_backend() == "cpu"  # interpret is refused on a TPU only


def test_failed_warmup_is_not_ready_and_fails_requests(run_async):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(ServingConfig(
            model="tiny", slots=2, max_seq_len=64, warmup_on_start=True,
        ))

        async def boom():
            raise RuntimeError("program did not build")

        engine._do_warmup = boom
        try:
            with pytest.raises(RuntimeError, match="did not build"):
                await engine.generate("hello", {"max-tokens": 2})
            assert engine._warmup_state() == "failed"
            assert engine.health()["ready"] is False
            # and every later request too: nothing is served lazily
            with pytest.raises(RuntimeError, match="did not build"):
                await engine.generate("again", {"max-tokens": 2})
        finally:
            await engine.close()

    run_async(main())
