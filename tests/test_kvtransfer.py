"""Disaggregated prefill/decode pools: the KV handoff plane e2e.

Layers covered: the wire format (round-trip property tests over fp32
and int8-row pools including a partial last block; version/magic/
fingerprint rejection), the engine pool roles (config round trip +
validation; the acceptance byte-identity — a request prefilled on a
``prefill``-role engine and decoded on a ``decode``-role engine matches
a combined engine token-for-token, with ``kv-export``/``kv-import``
flight events and a prefill-skipping admission pinned from
``request_timings``), capacity refusals (RESOURCE_EXHAUSTED-shaped
sheds → RateLimited → pod 503 + Retry-After → router retries the next
decode replica), the pod HTTP plane (``GET /kv/export/{request}`` /
``POST /kv/import``), the phase-aware router (per-pool eligibility,
last-pick phase, combined fleets bit-for-bit unchanged), the per-pool
autoscale specs + STS split manifests, and the chaos e2e over fake
kube: a prefill replica drains mid-handoff, the request requeues
front-of-class and completes on the surviving pool byte-identically —
zero loss.
"""

import asyncio
import json
import socket

import aiohttp
import numpy as np
import pytest

from langstream_tpu.serving import kvtransfer
from langstream_tpu.serving.kvtransfer import (
    LayoutMismatch,
    WIRE_MAGIC,
    WIRE_VERSION,
    check_fingerprint,
    deserialize_handoff,
    peek_header,
    prompt_digest,
    serialize_handoff,
)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _disagg_config(**overrides):
    from langstream_tpu.serving.engine import ServingConfig

    # f32 + paged: greedy streams are exactly shape-independent, so the
    # handoff's cross-engine continuation is bit-identical (the same
    # posture the drain/preemption byte-identity tests pin)
    base = dict(
        model="tiny", slots=2, max_seq_len=128, decode_chunk=4,
        model_dtype="float32", kv_layout="paged", kv_block_size=16,
        kv_pool_blocks=24, prefix_cache=False,
    )
    base.update(overrides)
    return ServingConfig(**base)


# --------------------------------------------------------------------------
# wire format: round trips + rejection
# --------------------------------------------------------------------------


def test_wire_roundtrip_fp32_and_partial_block():
    rng = np.random.default_rng(7)
    # 37 rows over block_size-16 blocks: a partial last block by design
    arrays = {
        "k": rng.standard_normal((2, 37, 8)).astype(np.float32),
        "v": rng.standard_normal((2, 37, 8)).astype(np.float32),
    }
    header = {
        "fingerprint": {"model": "tiny"},
        "request": "tiny-00000001",
        "prompt-digest": prompt_digest([1, 2, 3]),
        "kv-rows": 37,
    }
    payload = serialize_handoff(header, arrays)
    assert payload[:4] == WIRE_MAGIC
    back_header, back = deserialize_handoff(payload)
    assert back_header["request"] == "tiny-00000001"
    assert back_header["kv-rows"] == 37
    assert sorted(back) == ["k", "v"]
    for name in arrays:
        assert back[name].dtype == arrays[name].dtype
        np.testing.assert_array_equal(back[name], arrays[name])
    # peek parses the header without touching array bytes
    assert peek_header(payload)["prompt-digest"] == header["prompt-digest"]


def test_wire_roundtrip_int8_rows():
    rng = np.random.default_rng(11)
    arrays = {
        "k.q": rng.integers(-127, 127, (2, 21, 8), dtype=np.int8),
        "k.s": rng.standard_normal((2, 21, 2)).astype(np.float32),
        "v.q": rng.integers(-127, 127, (2, 21, 8), dtype=np.int8),
        "v.s": rng.standard_normal((2, 21, 2)).astype(np.float32),
    }
    payload = serialize_handoff({"kv-rows": 21}, arrays)
    _, back = deserialize_handoff(payload)
    assert sorted(back) == sorted(arrays)
    for name in arrays:
        assert back[name].dtype == arrays[name].dtype
        np.testing.assert_array_equal(back[name], arrays[name])


def test_wire_rejections():
    payload = serialize_handoff(
        {"kv-rows": 1}, {"k": np.zeros((1, 1, 4), np.float32)}
    )
    # bad magic
    with pytest.raises(LayoutMismatch, match="magic"):
        peek_header(b"XXXX" + payload[4:])
    # unsupported version
    bumped = (
        payload[:4]
        + (WIRE_VERSION + 1).to_bytes(4, "little")
        + payload[8:]
    )
    with pytest.raises(LayoutMismatch, match="wire version"):
        peek_header(bumped)
    # truncated array bytes
    with pytest.raises(LayoutMismatch, match="truncated"):
        deserialize_handoff(payload[:-3])
    # fingerprint disagreement names the keys
    ours = {"model": "tiny", "kv-block-size": 16, "dtype": "float32"}
    theirs = {"model": "tiny", "kv-block-size": 32, "dtype": "float32"}
    with pytest.raises(LayoutMismatch, match="kv-block-size"):
        check_fingerprint(ours, theirs)
    check_fingerprint(ours, dict(ours))  # identical: silent


def test_scatter_gather_roundtrip_partial_block_fp32_and_int8():
    """Pool-level property: rows written via the handoff scatter read
    back exactly through gather_kv — fp32 and pre-quantized int8 rows,
    with a partial last block."""
    import jax.numpy as jnp

    from langstream_tpu.models.paged import gather_kv

    rng = np.random.default_rng(3)
    L, bs, KhD, rows = 2, 8, 16, 19  # 19 rows -> 2 full + 1 partial block
    nrb = -(-rows // bs)
    table = np.array([1, 2, 3, 0], dtype=np.int32)

    # fp32 pools (distinct K and V arrays: both are donated)
    pool_k = jnp.zeros((L, 6, bs, KhD), jnp.float32)
    pool_v = jnp.zeros((L, 6, bs, KhD), jnp.float32)
    arrays = {
        "k": rng.standard_normal((L, rows, KhD)).astype(np.float32),
        "v": rng.standard_normal((L, rows, KhD)).astype(np.float32),
    }
    payload = serialize_handoff({"kv-rows": rows}, arrays)
    _, back = deserialize_handoff(payload)
    out_k, out_v = kvtransfer.scatter_slot(
        pool_k, pool_v, back, table, rows, padded_rows=24
    )
    for out, name in ((out_k, "k"), (out_v, "v")):
        gathered = np.asarray(
            gather_kv(out, jnp.asarray(table[None, :nrb]), nrb)
        )
        np.testing.assert_array_equal(gathered[:, 0, :rows], arrays[name])

    # int8 pools: quantized rows travel verbatim (bit-exact transit)
    make8 = lambda: {
        "q": jnp.zeros((L, 6, bs, KhD), jnp.int8),
        "s": jnp.zeros((L, 6, bs, 2), jnp.float32),
    }
    arrays8 = {
        "k.q": rng.integers(-127, 127, (L, rows, KhD), dtype=np.int8),
        "k.s": rng.standard_normal((L, rows, 2)).astype(np.float32),
        "v.q": rng.integers(-127, 127, (L, rows, KhD), dtype=np.int8),
        "v.s": rng.standard_normal((L, rows, 2)).astype(np.float32),
    }
    payload8 = serialize_handoff({"kv-rows": rows}, arrays8)
    _, back8 = deserialize_handoff(payload8)
    out_k8, out_v8 = kvtransfer.scatter_slot(
        make8(), make8(), back8, table, rows, padded_rows=24
    )
    for out, prefix in ((out_k8, "k"), (out_v8, "v")):
        gathered = gather_kv(out, jnp.asarray(table[None, :nrb]), nrb)
        np.testing.assert_array_equal(
            np.asarray(gathered["q"])[:, 0, :rows], arrays8[f"{prefix}.q"]
        )
        np.testing.assert_array_equal(
            np.asarray(gathered["s"])[:, 0, :rows], arrays8[f"{prefix}.s"]
        )


# --------------------------------------------------------------------------
# config: pool-role round trip + validation
# --------------------------------------------------------------------------


def test_pool_role_config_roundtrip_and_validation(monkeypatch):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    cfg = _disagg_config(pool_role="prefill")
    assert cfg.to_dict()["pool-role"] == "prefill"
    assert ServingConfig.from_dict(cfg.to_dict()) == cfg
    # default stays combined and round-trips
    assert ServingConfig.from_dict(_disagg_config().to_dict()).pool_role == (
        "combined"
    )
    # the StatefulSet split's env fallback: both pools share one config
    # secret, the role rides LS_POOL_ROLE
    monkeypatch.setenv("LS_POOL_ROLE", "decode")
    assert ServingConfig.from_dict({"model": "tiny"}).pool_role == "decode"
    monkeypatch.delenv("LS_POOL_ROLE")
    # an unknown role fails at construction, loudly
    with pytest.raises(ValueError, match="pool_role"):
        TpuServingEngine(_disagg_config(pool_role="both"))


# --------------------------------------------------------------------------
# the acceptance e2e: disaggregated == combined, byte for byte
# --------------------------------------------------------------------------


def test_disagg_byte_identity_e2e(run_async):
    from langstream_tpu.serving.engine import TpuServingEngine

    prompt = "disaggregated serving byte identity prompt"

    async def main():
        combined = TpuServingEngine(_disagg_config())
        baseline = await combined.generate(prompt, {"max-tokens": 12})
        await combined.close()

        pre = TpuServingEngine(_disagg_config(pool_role="prefill"))
        dec = TpuServingEngine(_disagg_config(pool_role="decode"))
        try:
            handoff = await pre.generate(prompt, {"max-tokens": 12})
            # the prefill engine returns a handoff ticket, not a
            # completion: first token only, finish_reason says so
            assert handoff["finish_reason"] == "handoff"
            assert handoff["tokens"] == baseline["tokens"][:1]
            assert pre.stats()["kvtransfer"]["exports"] == 1
            # the in-transit owner names the serialized payload's bytes
            owners = pre.stats()["attribution"]["memory"][
                "hbm_bytes_by_owner"
            ]
            assert owners["in-transit"] > 0

            payload = pre.take_export(handoff["handoff"])
            assert payload is not None
            assert (
                pre.stats()["attribution"]["memory"]["hbm_bytes_by_owner"][
                    "in-transit"
                ]
                == 0
            )
            # consumed exactly once
            assert pre.take_export(handoff["handoff"]) is None

            result = await dec.import_handoff(payload)
            # THE acceptance invariant: byte-identical greedy
            # tokens+text to the co-located run
            assert result["tokens"] == baseline["tokens"]
            assert result["text"] == baseline["text"]
            assert result["finish_reason"] == baseline["finish_reason"]

            # flight events carry bytes/blocks/ms on both sides
            export_ev = next(
                e for e in pre.flight.recent_events(0)
                if e["kind"] == "kv-export" and not e.get("warmup")
            )
            assert export_ev["bytes"] == len(payload)
            assert export_ev["blocks"] >= 1 and export_ev["ms"] >= 0
            import_ev = next(
                e for e in dec.flight.recent_events(0)
                if e["kind"] == "kv-import"
            )
            assert import_ev["bytes"] == len(payload)
            assert import_ev["request"] == handoff["handoff"]
            assert import_ev["digest"] == prompt_digest(_encode(pre, prompt))

            # the decode pod's admission SKIPPED prefill: pinned from
            # request_timings (the acceptance criterion's assert)
            timing = list(dec.request_timings)[-1]
            assert timing.get("imported") == 1.0
            assert timing["prefill"] < 0.05
            # the prefill pod's timing records the handoff
            pre_timing = list(pre.request_timings)[-1]
            assert pre_timing.get("handoff") == 1.0
            assert dec.stats()["kvtransfer"]["imports"] == 1
            # both sides expose their role on the stats surface
            assert pre.stats()["kvtransfer"]["role"] == "prefill"
            assert dec.stats()["kvtransfer"]["role"] == "decode"
        finally:
            await pre.close()
            await dec.close()

    run_async(main())


def _encode(engine, prompt: str) -> list[int]:
    tokens = engine.tokenizer.encode(prompt)
    max_prompt = engine.model_config.max_seq_len - 2
    return tokens[-max_prompt:] if len(tokens) > max_prompt else tokens


def test_disagg_int8_kv_byte_identity(run_async):
    """int8 KV pools hand off their quantized rows verbatim: the
    disaggregated stream matches the combined int8 run exactly."""
    from langstream_tpu.serving.engine import TpuServingEngine

    prompt = "int8 rows travel verbatim over the handoff"

    async def main():
        combined = TpuServingEngine(_disagg_config(kv_quantize="int8"))
        baseline = await combined.generate(prompt, {"max-tokens": 8})
        await combined.close()
        pre = TpuServingEngine(
            _disagg_config(kv_quantize="int8", pool_role="prefill")
        )
        dec = TpuServingEngine(
            _disagg_config(kv_quantize="int8", pool_role="decode")
        )
        try:
            handoff = await pre.generate(prompt, {"max-tokens": 8})
            payload = pre.take_export(handoff["handoff"])
            result = await dec.import_handoff(payload)
            assert result["tokens"] == baseline["tokens"]
            assert result["text"] == baseline["text"]
        finally:
            await pre.close()
            await dec.close()

    run_async(main())


def test_import_fingerprint_mismatch_rejected(run_async):
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        pre = TpuServingEngine(_disagg_config(pool_role="prefill"))
        # different block size = different layout: the import must refuse
        dec = TpuServingEngine(
            _disagg_config(
                pool_role="decode", kv_block_size=32, kv_pool_blocks=12
            )
        )
        try:
            handoff = await pre.generate("mismatch probe", {"max-tokens": 4})
            payload = pre.take_export(handoff["handoff"])
            with pytest.raises(LayoutMismatch, match="kv-block-size"):
                await dec.import_handoff(payload)
        finally:
            await pre.close()
            await dec.close()

    run_async(main())


def test_import_capacity_shed_is_explicit_retryable(run_async):
    """Satellite: a decode pool that cannot reserve the import's
    worst-case blocks sheds with RateLimited + retry hint (the pod maps
    it to 503 + Retry-After; the router retries the next replica) —
    never a request failure."""
    from langstream_tpu.serving.engine import TpuServingEngine
    from langstream_tpu.serving.qos import RateLimited

    async def main():
        pre = TpuServingEngine(_disagg_config(pool_role="prefill"))
        # a pool so small the worst case never fits an occupied engine:
        # 8 usable blocks x 16 rows = 128 max; one import wants
        # len(prompt)+max_tokens+1 but the pool is busy
        dec = TpuServingEngine(
            _disagg_config(pool_role="decode", kv_pool_blocks=9, slots=1)
        )
        try:
            h1 = await pre.generate(
                "capacity probe one", {"max-tokens": 100}
            )
            p1 = pre.take_export(h1["handoff"])
            h2 = await pre.generate(
                "capacity probe two", {"max-tokens": 100}
            )
            p2 = pre.take_export(h2["handoff"])
            # first import occupies the only slot + nearly all blocks;
            # don't await its completion — race the second import in
            t1 = asyncio.ensure_future(dec.import_handoff(p1))
            await asyncio.sleep(0.05)
            with pytest.raises(RateLimited) as exc:
                await dec.import_handoff(p2)
            assert exc.value.retry_after > 0
            assert exc.value.reason in (
                "kv-import-capacity", "no-free-slot"
            )
            assert dec.stats()["kvtransfer"]["import_sheds"] >= 1
            r1 = await t1
            assert r1["tokens"]
        finally:
            await pre.close()
            await dec.close()

    run_async(main())


# --------------------------------------------------------------------------
# pod HTTP plane: /kv/export/{request} + /kv/import
# --------------------------------------------------------------------------


def test_pod_kv_export_import_endpoints(run_async, monkeypatch):
    from langstream_tpu.runtime.pod import _serve_info
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    prompt = "pod plane handoff prompt"

    async def main():
        combined = TpuServingEngine(_disagg_config())
        baseline = await combined.generate(prompt, {"max-tokens": 6})
        await combined.close()

        pre = TpuServingEngine.get_or_create(
            _disagg_config(pool_role="prefill")
        )
        dec = TpuServingEngine.get_or_create(
            _disagg_config(pool_role="decode")
        )
        port = free_port()
        monkeypatch.setenv("LS_HTTP_PORT", str(port))
        server = await _serve_info(None)
        try:
            handoff = await pre.generate(prompt, {"max-tokens": 6})
            rid = handoff["handoff"]
            base = f"http://127.0.0.1:{port}"
            async with aiohttp.ClientSession() as session:
                # pickup: exactly once, then 404
                async with session.get(f"{base}/kv/export/{rid}") as resp:
                    assert resp.status == 200
                    assert resp.headers["Content-Type"] == (
                        "application/octet-stream"
                    )
                    payload = await resp.read()
                async with session.get(f"{base}/kv/export/{rid}") as resp:
                    assert resp.status == 404
                # landing: the full generation result comes back
                async with session.post(
                    f"{base}/kv/import", data=payload
                ) as resp:
                    assert resp.status == 200
                    result = await resp.json()
                assert result["tokens"] == baseline["tokens"]
                assert result["text"] == baseline["text"]
                # garbage payload → 409 (a refusal, not a retry)
                async with session.post(
                    f"{base}/kv/import", data=b"not a handoff"
                ) as resp:
                    assert resp.status == 409
                    body = await resp.json()
                    assert "magic" in body["error"]
        finally:
            server.close()
            await pre.close()
            await dec.close()

    run_async(main())


# --------------------------------------------------------------------------
# phase-aware router (satellite: per-pool stats + last-pick phase)
# --------------------------------------------------------------------------


def _snap(name, pool="combined", queued=0, occupancy=0, **kw):
    return {
        "replica": name, "pool": pool, "queued": queued,
        "occupancy": occupancy, "slots": 4, **kw,
    }


def test_router_phase_filtering_and_pool_stats():
    from langstream_tpu.gateway.router import ReplicaRouter

    clock = [0.0]
    router = ReplicaRouter(clock=lambda: clock[0])
    router.observe(
        [
            _snap("app-prefill-0", "prefill", queued=5),
            _snap("app-prefill-1", "prefill"),
            _snap("app-decode-0", "decode"),
            _snap("app-decode-1", "decode", draining=True),
        ]
    )
    # new requests land on the prefill pool (least loaded)
    assert router.pick(phase="prefill") == "app-prefill-1"
    assert router.last_pick_phase == "prefill"
    # handoff targets come from HEALTHY decode replicas only — the
    # draining one is never eligible
    assert router.pick(phase="decode") == "app-decode-0"
    assert router.last_pick_phase == "decode"
    # exclusion: a 503 from the only healthy decode replica leaves None
    # (the caller knows the pool is saturated, nothing silently loops)
    assert router.pick(phase="decode", exclude={"app-decode-0"}) is None
    # satellite: per-pool eligibility counts + last-pick phase in stats
    stats = router.stats()
    assert stats["pools"]["prefill"] == {"replicas": 2, "eligible": 2}
    assert stats["pools"]["decode"] == {"replicas": 2, "eligible": 1}
    assert stats["last_pick_phase"] == "decode"
    assert stats["replicas"]["app-decode-1"]["pool"] == "decode"


def test_router_combined_fleet_ignores_phase():
    """A classic all-combined fleet routes bit-for-bit as before: the
    phase filter only engages once a split pool exists."""
    from langstream_tpu.gateway.router import ReplicaRouter

    clock = [0.0]
    router = ReplicaRouter(clock=lambda: clock[0])
    router.observe([_snap("app-ai-0", queued=3), _snap("app-ai-1")])
    assert router.pick() == "app-ai-1"
    assert router.pick(phase="prefill") == "app-ai-1"
    assert router.pick(phase="decode") == "app-ai-1"
    assert router.stats()["pools"] == {
        "combined": {"replicas": 2, "eligible": 2}
    }


def test_router_decode_picks_skip_tenant_affinity():
    from langstream_tpu.gateway.router import ReplicaRouter

    clock = [0.0]
    router = ReplicaRouter(clock=lambda: clock[0])
    router.observe(
        [
            _snap("app-prefill-0", "prefill"),
            _snap("app-decode-0", "decode", queued=9),
            _snap("app-decode-1", "decode"),
        ]
    )
    # the tenant pins to its prefill replica...
    assert router.pick("alice", phase="prefill") == "app-prefill-0"
    # ...and decode picks stay pure least-loaded (no pin thrash)
    assert router.pick("alice", phase="decode") == "app-decode-1"
    assert router.pick("alice", phase="prefill") == "app-prefill-0"
    assert router.affinity_hits >= 1


# --------------------------------------------------------------------------
# per-pool autoscaling + STS split
# --------------------------------------------------------------------------


class _Res:
    def __init__(self, type_, configuration):
        self.type = type_
        self.configuration = configuration


class _App:
    def __init__(self, resources):
        self.resources = resources


def test_pool_autoscale_specs_and_defaults():
    from langstream_tpu.controlplane.autoscaler import (
        application_autoscale_specs,
        pool_autoscale_spec,
    )

    app = _App(
        {
            "serving": _Res(
                "tpu-serving-configuration",
                {
                    "pools": {
                        "prefill": {
                            "autoscale": {"min-replicas": 1,
                                          "max-replicas": 4},
                        },
                        "decode": {
                            "autoscale": {"min-replicas": 2,
                                          "max-replicas": 8},
                        },
                    }
                },
            )
        }
    )
    specs = {s.pool: s for s in application_autoscale_specs(app)}
    assert set(specs) == {"prefill", "decode"}
    # prefill scales on queue depth: its KV signal can never fire
    assert specs["prefill"].kv_reserved == 1.0
    assert specs["prefill"].queue_depth_per_replica == 8.0
    # decode scales on KV reserved fraction: queue thresholds parked
    assert specs["decode"].kv_reserved == 0.85
    assert specs["decode"].queue_depth_per_replica >= 1e9
    assert specs["decode"].min_replicas == 2
    # explicit overrides win over the role defaults
    spec = pool_autoscale_spec(
        "decode", {"autoscale": {"kv-reserved": 0.5}}
    )
    assert spec.kv_reserved == 0.5 and spec.pool == "decode"
    # a pool without an autoscale section is declared but not scaled
    assert pool_autoscale_spec("prefill", {}) is None


def test_pools_validation_rejects_bad_roles_and_sections():
    from langstream_tpu.controlplane.autoscaler import (
        validate_application_autoscale,
    )

    bad_role = _App(
        {
            "s": _Res(
                "tpu-serving-configuration",
                {"pools": {"verify": {}}},
            )
        }
    )
    with pytest.raises(ValueError, match="verify"):
        validate_application_autoscale(bad_role)
    bad_section = _App(
        {
            "s": _Res(
                "tpu-serving-configuration",
                {"pools": {"prefill": {"autoscale": {"min-replicas": 0}}}},
            )
        }
    )
    with pytest.raises(ValueError, match="min-replicas"):
        validate_application_autoscale(bad_section)
    # a classic (pool-less) autoscale section still validates
    validate_application_autoscale(
        _App(
            {
                "s": _Res(
                    "tpu-serving-configuration",
                    {"autoscale": {"min-replicas": 1}},
                )
            }
        )
    )


def test_observation_from_summary_carries_pool_role():
    from langstream_tpu.controlplane.autoscaler import (
        observation_from_summary,
    )

    obs = observation_from_summary(
        "app-decode-0",
        [{"model": "tiny", "slots": 4, "pool_role": "decode",
          "scheduler": {}, "health": {}, "summary": {}}],
    )
    assert obs.pool == "decode"
    assert obs.to_dict()["pool"] == "decode"
    # pre-disagg summaries default to combined
    obs = observation_from_summary(
        "app-ai-0", [{"model": "tiny", "slots": 4}]
    )
    assert obs.pool == "combined"


def test_statefulset_pool_split_manifests():
    from langstream_tpu.k8s.crds import (
        AgentCustomResource,
        AgentResourcesCR,
        AgentSpec,
    )
    from langstream_tpu.k8s.resources import AgentResourcesFactory

    cr = AgentCustomResource(
        name="chat-ai",
        namespace="langstream-t1",
        spec=AgentSpec(
            tenant="t1",
            application_id="chat",
            agent_id="ai",
            image="img",
            agent_config_secret_ref="chat-ai-config",
            agent_config_secret_ref_checksum="abc",
            resources=AgentResourcesCR(parallelism=2, size=1),
            options={"poolRoles": {"prefill": 1, "decode": 3}},
        ),
    )
    stss = AgentResourcesFactory.generate_statefulsets(cr)
    by_name = {s["metadata"]["name"]: s for s in stss}
    assert set(by_name) == {"chat-ai-decode", "chat-ai-prefill"}
    assert by_name["chat-ai-decode"]["spec"]["replicas"] == 3
    assert by_name["chat-ai-prefill"]["spec"]["replicas"] == 1
    for role, sts in (("decode", by_name["chat-ai-decode"]),
                      ("prefill", by_name["chat-ai-prefill"])):
        env = {
            e["name"]: e.get("value")
            for e in sts["spec"]["template"]["spec"]["containers"][0]["env"]
        }
        assert env["LS_POOL_ROLE"] == role
    # PDBs ride the split: one per pool STS
    pdbs = AgentResourcesFactory.generate_pod_disruption_budgets(cr, stss)
    assert {p["metadata"]["name"] for p in pdbs} == set(by_name)
    # a list spelling means parallelism replicas per pool
    cr.spec.options = {"poolRoles": ["prefill", "decode"]}
    stss = AgentResourcesFactory.generate_statefulsets(cr)
    assert all(s["spec"]["replicas"] == 2 for s in stss)
    # unknown roles fail the reconcile loudly
    cr.spec.options = {"poolRoles": ["verify"]}
    with pytest.raises(ValueError, match="verify"):
        AgentResourcesFactory.generate_statefulsets(cr)
    # multi-host slices cannot split (their replicas are slice hosts)
    cr.spec.options = {"poolRoles": ["prefill", "decode"]}
    cr.spec.resources = AgentResourcesCR(
        parallelism=1, size=1, device_mesh={"tp": 8}
    )
    with pytest.raises(ValueError, match="multi-host"):
        AgentResourcesFactory.generate_statefulsets(cr)


def test_fleet_backend_resolves_pool_statefulset():
    from langstream_tpu.controlplane.autoscaler import AutoscaleSpec
    from langstream_tpu.k8s.compute import StatefulSetFleetBackend

    class _Runtime:
        def serving_statefulsets(self, tenant, name):
            return [
                {"metadata": {"name": "chat-ai-prefill"}},
                {"metadata": {"name": "chat-ai-decode"}},
            ]

    spec = AutoscaleSpec(pool="decode")
    backend = StatefulSetFleetBackend(_Runtime(), "t1", "chat", spec)
    assert backend.resolve() == "chat-ai-decode"
    spec = AutoscaleSpec(pool="prefill", agent="ai")
    backend = StatefulSetFleetBackend(_Runtime(), "t1", "chat", spec)
    assert backend.resolve() == "chat-ai-prefill"
    # pool spec round-trips through the kebab dict like its siblings
    assert AutoscaleSpec.from_dict(spec.to_dict()) == spec


# --------------------------------------------------------------------------
# graftcheck POOL701: TP/TN beyond the registry fixtures
# --------------------------------------------------------------------------


def test_pool701_scope_and_sanctioned_fetch():
    import textwrap

    from langstream_tpu.analysis import ALL_RULES, analyze_source

    path = "langstream_tpu/serving/kvtransfer.py"
    sync_in_serialize = textwrap.dedent(
        """
        import jax

        def serialize_handoff(header, gathered):
            jax.block_until_ready(gathered)
            return b""
        """
    )
    ids = [f.rule for f in analyze_source(sync_in_serialize, path, ALL_RULES)]
    assert "POOL701" in ids
    # the sanctioned _fetch* stage stays silent
    sanctioned = textwrap.dedent(
        """
        import jax

        def _fetch_rows(gathered):
            jax.block_until_ready(gathered)
            return gathered
        """
    )
    assert [
        f.rule for f in analyze_source(sanctioned, path, ALL_RULES)
    ] == []
    # nested dispatch-thread closures are exempt (the engine pattern)
    nested = textwrap.dedent(
        """
        import jax

        def deserialize_handoff(data):
            def _run():
                jax.block_until_ready(data)
            return _run
        """
    )
    assert [f.rule for f in analyze_source(nested, path, ALL_RULES)] == []
    # the pod payload builder is policed too
    pod = textwrap.dedent(
        """
        def _kv_export_payload(rid):
            with open("/tmp/kv") as f:
                return f.read()
        """
    )
    ids = [
        f.rule
        for f in analyze_source(pod, "langstream_tpu/runtime/pod.py", ALL_RULES)
    ]
    assert "POOL701" in ids
    # other modules are out of scope
    assert (
        analyze_source(
            sync_in_serialize, "langstream_tpu/gateway/server.py", ALL_RULES
        )
        == []
    )


# --------------------------------------------------------------------------
# chaos e2e over fake kube: drain mid-handoff, zero loss
# --------------------------------------------------------------------------


class FakePoolBackend:
    """A fake-kube prefill pool: the StatefulSet lives in
    InMemoryKubeApi, each 'pod' is a REAL prefill-role engine — so the
    scale-down exercises the true drain/preempt/requeue machinery
    mid-handoff while the cluster state stays scripted (the PR 9 chaos
    template, pointed at the disaggregated split)."""

    def __init__(self, api, namespace, sts_name, config):
        self.api = api
        self.namespace = namespace
        self.sts_name = sts_name
        self.config = config
        self.engines = {}
        self.calls = []
        self._sync_engines()

    def _sts(self):
        return self.api.get("StatefulSet", self.namespace, self.sts_name)

    def replicas(self) -> int:
        return int(self._sts()["spec"]["replicas"])

    def _sync_engines(self):
        from langstream_tpu.serving.engine import TpuServingEngine

        for i in range(self.replicas()):
            pod = f"{self.sts_name}-{i}"
            if pod not in self.engines:
                self.engines[pod] = TpuServingEngine(self.config)

    def observe(self):
        out = []
        for i in range(self.replicas()):
            pod = f"{self.sts_name}-{i}"
            engine = self.engines.get(pod)
            stats = engine.stats()
            health = stats["health"]
            out.append(
                {
                    "replica": pod,
                    "queued": stats["queued"],
                    "occupancy": stats["active"],
                    "slots": stats["slots"],
                    "state": health["state"],
                    "draining": health["draining"],
                    "pool": "prefill",
                }
            )
        return out

    def set_replicas(self, n: int):
        self.calls.append(("set_replicas", n))
        sts = self._sts()
        sts["spec"]["replicas"] = int(n)
        self.api.apply(sts)

    async def drain(self, replica: str, grace_s: float):
        self.calls.append(("drain", replica))
        engine = self.engines.get(replica)
        if engine is None:
            return None
        return await engine.drain(grace_s)

    async def close(self):
        for engine in self.engines.values():
            await engine.close()


def test_chaos_prefill_drain_mid_handoff_zero_loss(run_async):
    """The satellite chaos e2e: a prefill replica drains while a
    request is mid-prefill (mid-handoff). The drain preempts and
    requeues it front-of-class; it completes its prefill + export on
    the draining replica inside the grace budget (zero loss), the
    decode pool imports the payload, and the final stream is
    byte-identical to a co-located run. The router never offers the
    draining replica for new prefill traffic."""
    from langstream_tpu.controlplane.autoscaler import FleetAutoscaler
    from langstream_tpu.controlplane.autoscaler import pool_autoscale_spec
    from langstream_tpu.gateway.router import ReplicaRouter
    from langstream_tpu.k8s.client import InMemoryKubeApi
    from langstream_tpu.serving.engine import TpuServingEngine

    # chunked prefill: a long prompt spans several loop passes, so the
    # drain reliably lands mid-prefill (mid-handoff)
    config = _disagg_config(
        pool_role="prefill", prefill_chunk=8, max_seq_len=256,
        kv_pool_blocks=40,
    )
    # ~124 byte-tokens over 8-token prefill chunks: 15+ loop passes, so
    # the drain reliably lands while the prefill is still in flight
    prompt = "chaos drain mid handoff prompt " * 4
    spec = pool_autoscale_spec(
        "prefill",
        {
            "autoscale": {
                "min-replicas": 1, "max-replicas": 2,
                "scale-up-window-s": 0, "scale-down-window-s": 0,
                "cooldown-s": 0, "drain-grace-s": 120,
                "idle-occupancy": 0.9,
            }
        },
    )

    api = InMemoryKubeApi()
    api.apply(
        {
            "apiVersion": "apps/v1",
            "kind": "StatefulSet",
            "metadata": {
                "name": "chat-ai-prefill",
                "namespace": "langstream-t1",
                "labels": {"langstream-application": "chat"},
            },
            "spec": {"serviceName": "chat-ai", "replicas": 2,
                     "template": {"spec": {"containers": [{}]}}},
        }
    )

    async def main():
        # byte-identity baseline: the same request co-located
        combined = TpuServingEngine(
            _disagg_config(
                prefill_chunk=8, max_seq_len=256, kv_pool_blocks=40
            )
        )
        baseline = await combined.generate(prompt, {"max-tokens": 10})
        await combined.close()

        backend = FakePoolBackend(
            api, "langstream-t1", "chat-ai-prefill", config
        )
        decode = TpuServingEngine(
            _disagg_config(
                pool_role="decode", max_seq_len=256, kv_pool_blocks=40
            )
        )
        scaler = FleetAutoscaler(spec, backend)
        try:
            victim = backend.engines["chat-ai-prefill-1"]
            task = asyncio.ensure_future(
                victim.generate(prompt, {"max-tokens": 10})
            )
            # wait until the victim is genuinely mid-prefill
            for _ in range(2000):
                if any(s.prefilling for s in victim.slots):
                    break
                await asyncio.sleep(0.005)
            assert any(s.prefilling for s in victim.slots), (
                "drain must land mid-handoff"
            )
            entry = await scaler.step()
            assert entry is not None and entry["action"] == "down", entry
            assert entry["outcome"] == "scaled"
            assert entry["victim"] == "chat-ai-prefill-1"
            # drain-before-terminate ordering held
            assert backend.calls[-2:] == [
                ("drain", "chat-ai-prefill-1"),
                ("set_replicas", 1),
            ]
            drain_report = entry["drain"]
            # the mid-handoff request was requeued front-of-class and
            # COMPLETED (export produced) — zero loss, nothing shed
            assert drain_report["requeued"] >= 1
            assert drain_report["shed"] == 0
            assert drain_report["completed"] >= 1
            events = victim.flight.recent_events(0)
            assert any(
                e.get("reason") == "drain"
                for e in events
                if e["kind"] == "preempt"
            )
            handoff = await asyncio.wait_for(task, timeout=60)
            assert handoff["finish_reason"] == "handoff"
            # the survivor pool serves the handoff: byte-identical
            payload = victim.take_export(handoff["handoff"])
            assert payload is not None
            result = await decode.import_handoff(payload)
            assert result["tokens"] == baseline["tokens"]
            assert result["text"] == baseline["text"]
            # the router never offers the drained replica for prefill
            router = ReplicaRouter()
            router.observe(
                backend.observe()
                + [{"replica": "chat-ai-decode-0", "pool": "decode",
                    "queued": 0, "occupancy": 0, "slots": 2}]
            )
            assert router.pick(phase="prefill") == "chat-ai-prefill-0"
            assert router.pick(phase="decode") == "chat-ai-decode-0"
            # new arrivals on the drained replica shed explicitly with a
            # retry hint — the gateway resends to the survivor
            from langstream_tpu.serving.qos import RateLimited

            with pytest.raises(RateLimited) as exc:
                await victim.generate("late arrival", {"max-tokens": 2})
            assert exc.value.retry_after > 0
            json.dumps(scaler.status())  # serializable operator surface
        finally:
            await backend.close()
            await decode.close()

    run_async(main())
