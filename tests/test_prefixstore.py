"""Tiered prefix-KV store e2e (serving/prefixstore.py, docs/PREFIX.md).

Layers covered: the spec (kebab round trip + deploy-time validation
rejects), the T2 storage backends, the store's tier mechanics (LRU
budgets, demotion cascade, hydration, fingerprint refusal-and-delete,
pinning), the exact-ledger property test (byte conservation across any
demote/promote/evict sequence), the engine integration (T0→T1→T2
demotion at the loop safe point, T1 promotion + T2 hydration at
admission — greedy tokens+text byte-identical to a cold-computed run
for fp32 AND int8 paged pools), the chaos leg (eviction storm + a
mid-hydration drain leaves the ledgers exactly summing, zero silent
loss; prefix-store-less engines byte-identical to pre-tier behavior),
the router's prefix affinity, the gateway digest stamp, and the
warm-prefix bench phase (the acceptance e2e: replica B's first shared-
prefix request hydrates from T2 with TTFT under its cold-compute
baseline, and the router's ``prefix_hits`` shows repeat traffic landing
back on the replica holding the blocks).
"""

import asyncio
import random

import numpy as np
import pytest

from langstream_tpu.serving.prefixstore import (
    LocalDiskPrefixStorage,
    PrefixStore,
    PrefixStoreSpec,
    make_prefix_storage,
    prefix_digest_for_text,
    validate_application_prefix_store,
)

FINGERPRINT = {
    "model": "tiny",
    "dtype": "float32",
    "kv-quantize": None,
    "kv-block-size": 16,
    "layers": 2,
    "kv-heads": 2,
    "head-dim": 8,
    "max-seq-len": 256,
}


def _spec(tmp_path=None, **overrides):
    d = {
        "t0-bytes": 0,
        "t1-bytes": 1 << 20,
        "t2-rescan-s": 0.1,
        "hydrate-timeout-s": 5.0,
    }
    if tmp_path is not None:
        d["t2"] = {"type": "local", "path": str(tmp_path)}
    d.update(overrides)
    return PrefixStoreSpec.from_dict(d)


def _store(tmp_path=None, **overrides) -> PrefixStore:
    return PrefixStore(
        _spec(tmp_path, **overrides),
        fingerprint=dict(FINGERPRINT),
        block_bytes=2048,
        rows_per_block=16,
    )


def _arrays(seed: int, nbytes: int = 2048) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    half = nbytes // 8
    return {
        "k": rng.standard_normal(half).astype(np.float32),
        "v": rng.standard_normal(half).astype(np.float32),
    }


# --------------------------------------------------------------------------
# spec + validation
# --------------------------------------------------------------------------


def test_spec_roundtrip_and_defaults():
    spec = _spec(t2=None)
    back = PrefixStoreSpec.from_dict(spec.to_dict())
    assert back == spec
    assert PrefixStoreSpec.from_dict(None) is None
    full = PrefixStoreSpec.from_dict(
        {
            "enabled": True,
            "t0-bytes": 1024,
            "t1-bytes": 4096,
            "t2-bytes": 1 << 30,
            "t2": {"type": "local", "path": "/tmp/x"},
            "hydrate-timeout-s": 2.5,
            "t2-rescan-s": 1.0,
        }
    )
    assert PrefixStoreSpec.from_dict(full.to_dict()) == full
    assert full.t2_config() == {"type": "local", "path": "/tmp/x"}


@pytest.mark.parametrize(
    "bad",
    [
        {"t1-bytes": 0},
        {"t0-bytes": -1},
        {"t2-bytes": -5},
        {"hydrate-timeout-s": 0},
        {"t2-rescan-s": -1},
        {"t2": {"type": "ftp"}},
        {"t2": "not-a-mapping"},
        {"unknown-key": 1},
    ],
)
def test_spec_validation_rejects(bad):
    with pytest.raises(ValueError):
        PrefixStoreSpec.from_dict(bad)


def test_validate_application_prefix_store():
    class Res:
        type = "tpu-serving-configuration"

        def __init__(self, conf):
            self.configuration = conf

    class App:
        def __init__(self, conf):
            self.resources = {"tpu": Res(conf)}

    validate_application_prefix_store(App({"prefix-store": None}))
    validate_application_prefix_store(
        App({"prefix-store": {"t1-bytes": 4096}})
    )
    with pytest.raises(ValueError, match="prefix-store"):
        validate_application_prefix_store(
            App({"prefix-store": {"t1-bytes": -1}})
        )


def test_engine_config_requires_prefix_cache():
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    with pytest.raises(ValueError, match="prefix-cache"):
        TpuServingEngine(
            ServingConfig(
                model="tiny", slots=1, max_seq_len=64,
                kv_block_size=16, prefix_cache=False,
                prefix_store=_spec(t2=None),
            )
        )


# --------------------------------------------------------------------------
# storage backends
# --------------------------------------------------------------------------


def test_local_disk_storage_roundtrip(tmp_path):
    storage = LocalDiskPrefixStorage(tmp_path)
    assert storage.get("aa11") is None
    storage.put("aa11", b"payload-1")
    storage.put("bb22", b"payload-2")
    assert storage.get("aa11") == b"payload-1"
    assert storage.list_keys() == ["aa11", "bb22"]
    storage.delete("aa11")
    assert storage.get("aa11") is None
    assert storage.list_keys() == ["bb22"]
    for bad in ("", "a/b", "..", "a.b"):
        with pytest.raises(ValueError):
            storage.put(bad, b"x")


def test_make_prefix_storage_factory(tmp_path):
    assert make_prefix_storage(None) is None
    assert make_prefix_storage({}) is None
    local = make_prefix_storage({"type": "local", "path": str(tmp_path)})
    assert isinstance(local, LocalDiskPrefixStorage)
    with pytest.raises(ValueError):
        make_prefix_storage({"type": "local"})  # no path
    with pytest.raises(ValueError):
        make_prefix_storage({"type": "gcs"})


# --------------------------------------------------------------------------
# store tier mechanics
# --------------------------------------------------------------------------


def test_t1_insert_take_and_lru_eviction_without_t2():
    store = _store(None, **{"t1-bytes": 5000})  # room for two 2KB entries
    store.insert_t1("d1", "", _arrays(1))
    store.insert_t1("d2", "d1", _arrays(2))
    assert store.t1_has("d1") and store.t1_has("d2")
    # third insert pushes over budget: d1 (LRU) evicts — counted
    store.insert_t1("d3", "d2", _arrays(3))
    assert not store.t1_has("d1")
    assert store.evictions == 1 and store.evicted_bytes == 2048
    events = dict(store.drain_events())
    assert events.get("prefix-evict", {}).get("reason") == "t1-budget"
    # take removes and counts a hit; a second take misses
    entry = store.take_t1("d2")
    assert entry is not None and entry["parent"] == "d1"
    assert store.take_t1("d2") is None
    assert store.t1_hits == 1 and store.t1_misses == 1
    assert store.t1_bytes == 2048  # only d3 left
    store.close()


def test_demotion_cascade_to_t2_and_hydration(tmp_path):
    store = _store(tmp_path, **{"t1-bytes": 1})
    store.insert_t1("d1", "", _arrays(1))
    store.insert_t1("d2", "d1", _arrays(2))
    assert store.flush(10)
    store.apply_results()
    assert store.t2_has("d1") and store.t2_has("d2")
    assert store.t1_bytes == 0 and store.in_transit_bytes == 0
    assert store.t2_bytes == 4096
    assert store.demotions_t1_t2 == 2
    # a second store over the same path discovers the blobs by scan
    other = _store(tmp_path, **{"t1-bytes": 1 << 20})
    assert other.flush(10)
    other.apply_results()
    assert other.t2_has("d1") and other.t2_has("d2")
    assert other.request_hydration(["d1", "d2"]) == 2
    assert other.flush(10)
    other.apply_results()
    assert other.t1_has("d1") and other.t1_has("d2")
    assert other.hydrations == 2 and other.hydrate_failures == 0
    got = other.take_t1("d1")
    np.testing.assert_array_equal(got["arrays"]["k"], _arrays(1)["k"])
    store.close()
    other.close()


def test_fingerprint_mismatch_refused_and_deleted(tmp_path):
    store = _store(tmp_path, **{"t1-bytes": 1})
    store.insert_t1("d1", "", _arrays(1))
    assert store.flush(10)
    store.apply_results()
    # a store with a DIFFERENT layout fingerprint must refuse the blob
    # and delete it — never half-hydrate foreign-geometry rows
    other = PrefixStore(
        _spec(tmp_path, **{"t1-bytes": 1 << 20}),
        fingerprint=dict(FINGERPRINT, **{"kv-block-size": 64}),
        block_bytes=2048,
        rows_per_block=64,
    )
    assert other.flush(10)
    other.apply_results()
    assert other.request_hydration(["d1"]) == 1
    assert other.flush(10)
    other.apply_results()
    assert other.fingerprint_refusals == 1
    assert not other.t1_has("d1")
    assert not other.t2_has("d1")
    # the blob is GONE from storage, not just skipped
    assert LocalDiskPrefixStorage(tmp_path).get("d1") is None
    store.close()
    other.close()


def test_corrupt_blob_refused(tmp_path):
    storage = LocalDiskPrefixStorage(tmp_path)
    storage.put("feed", b"not a kv payload at all")
    store = _store(tmp_path)
    assert store.flush(10)
    store.apply_results()
    assert store.t2_has("feed")
    store.request_hydration(["feed"])
    assert store.flush(10)
    store.apply_results()
    assert store.hydrate_failures == 1 and not store.t1_has("feed")
    assert storage.get("feed") is None  # deleted, never retried forever
    store.close()


def test_t2_byte_budget_trims_oldest(tmp_path):
    store = _store(tmp_path, **{"t1-bytes": 1, "t2-bytes": 5000})
    for i in range(4):
        store.insert_t1(f"d{i}", "", _arrays(i))
        assert store.flush(10)
        store.apply_results()
    # 4 × 2KB payloads against a 5KB budget: the two oldest trimmed
    assert store.t2_bytes <= 5000
    assert not store.t2_has("d0") and not store.t2_has("d1")
    assert store.t2_has("d2") and store.t2_has("d3")
    assert store.flush(10)
    assert LocalDiskPrefixStorage(tmp_path).get("d0") is None
    store.close()


def test_hydrated_entries_pinned_against_shrink(tmp_path):
    clock = [0.0]
    store = PrefixStore(
        _spec(tmp_path, **{"t1-bytes": 1, "hydrate-timeout-s": 5.0}),
        fingerprint=dict(FINGERPRINT),
        block_bytes=2048,
        rows_per_block=16,
        clock=lambda: clock[0],
    )
    store.insert_t1("d1", "", _arrays(1))
    assert store.flush(10)
    store.apply_results()
    store.request_hydration(["d1"])
    assert store.flush(10)
    store.apply_results()
    # the hydrated entry sits over the 1-byte budget but is PINNED: the
    # admission that asked for it must find it
    assert store.t1_has("d1")
    # past the pin window it shrinks normally
    clock[0] = 6.0
    store.insert_t1("dx", "", _arrays(9))
    assert not store.t1_has("d1")
    store.close()


# --------------------------------------------------------------------------
# ledger conservation property test
# --------------------------------------------------------------------------


def test_ledger_conservation_property(tmp_path):
    """T1+in-transit+T2 byte ledgers sum exactly across ANY random
    demote/promote/evict/hydrate sequence — every byte that enters is
    either resident in a tier, was taken by a promotion, or was evicted
    with its reason counted. Zero silent loss, by construction."""
    rng = random.Random(11)
    store = _store(tmp_path, **{"t1-bytes": 6000, "t2-bytes": 9000})
    digests = [f"p{i:02d}" for i in range(24)]
    for step in range(300):
        op = rng.random()
        d = rng.choice(digests)
        if op < 0.45:
            store.insert_t1(d, "", _arrays(rng.randrange(1000)))
        elif op < 0.65:
            store.take_t1(d)
        elif op < 0.85:
            store.request_hydration([d])
        else:
            store.apply_results()
        if step % 40 == 0:
            store.flush(10)
            store.apply_results()
        ledger = store.ledger()
        resident = (
            ledger["t1_bytes"]
            + ledger["in_transit_bytes"]
            + ledger["t2_bytes"]
        )
        flows = (
            ledger["inserted_bytes"]
            + ledger["discovered_bytes"]
            - ledger["taken_bytes"]
            - ledger["evicted_bytes"]
        )
        assert resident == flows, (step, ledger)
        # internal exactness: the ledgers match the containers
        assert ledger["t1_bytes"] == sum(
            e["nbytes"] for e in store._t1.values()
        )
        assert ledger["in_transit_bytes"] == sum(
            e["nbytes"] for e in store._t2_inflight.values()
        )
        assert ledger["t2_bytes"] == sum(store._t2_index.values())
    store.flush(10)
    store.apply_results()
    store.close()


# --------------------------------------------------------------------------
# gateway digest + router affinity
# --------------------------------------------------------------------------


def test_prefix_digest_for_text():
    shared = "s" * 600
    assert prefix_digest_for_text(None) is None
    assert prefix_digest_for_text("short") is None
    a = prefix_digest_for_text(shared + " tail one")
    b = prefix_digest_for_text(shared + " completely different tail")
    assert a and a == b
    assert prefix_digest_for_text("x" + shared) != a


def test_router_prefix_affinity():
    from langstream_tpu.gateway.router import ReplicaRouter

    r = ReplicaRouter()
    fleet = [
        {"replica": "app-ai-0", "queued": 0, "occupancy": 0, "slots": 4},
        {"replica": "app-ai-1", "queued": 5, "occupancy": 4, "slots": 4},
    ]
    r.observe(fleet)
    digest = prefix_digest_for_text("p" * 600)
    assert r.pick("t1", prefix=digest) == "app-ai-0"
    # load inverts: the prefix pin holds — even for a DIFFERENT tenant
    r.observe([
        {"replica": "app-ai-0", "queued": 9, "occupancy": 4, "slots": 4},
        {"replica": "app-ai-1", "queued": 0, "occupancy": 0, "slots": 4},
    ])
    assert r.pick("t2", prefix=digest) == "app-ai-0"
    stats = r.stats()
    assert stats["prefix_hits"] == 1
    assert stats["pinned_prefixes"] == 1
    # prefix-less traffic keeps the pre-tier least-loaded choice
    assert r.pick("t3") == "app-ai-1"
    # the pinned replica drains: the pin breaks, traffic re-pins
    r.observe([
        {
            "replica": "app-ai-0", "queued": 0, "occupancy": 0,
            "slots": 4, "draining": True,
        },
        {"replica": "app-ai-1", "queued": 0, "occupancy": 0, "slots": 4},
    ])
    assert r.pick("t2", prefix=digest) == "app-ai-1"
    assert r.stats()["prefix_rerouted"] == 1
    # and the repeat follows the NEW pin
    assert r.pick("t9", prefix=digest) == "app-ai-1"
    assert r.stats()["prefix_hits"] == 2


def test_gateway_stamp_includes_prefix_header():
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer
    from langstream_tpu.serving.prefixstore import PREFIX_HEADER

    registry = GatewayRegistry()
    registry.update_fleet("t", "app", [
        {"replica": "app-ai-0", "queued": 0, "occupancy": 0, "slots": 4},
    ])
    server = GatewayServer(registry=registry, port=0)
    headers: dict = {}
    value = "v" * 600
    server._stamp_replica(headers, "t", "app", {}, {}, value=value)
    assert headers[PREFIX_HEADER] == prefix_digest_for_text(value)
    assert headers["langstream-replica"] == "app-ai-0"
    # short values stamp neither header key nor break routing
    headers2: dict = {}
    server._stamp_replica(headers2, "t", "app", {}, {}, value="short")
    assert PREFIX_HEADER not in headers2


# --------------------------------------------------------------------------
# engine integration: demote → promote → hydrate, byte-identical
# --------------------------------------------------------------------------


def _engine_config(tmp_path, kv_quantize=None, **overrides):
    from langstream_tpu.serving.engine import ServingConfig

    base = dict(
        model="tiny", slots=2, max_seq_len=256, decode_chunk=4,
        model_dtype="float32", kv_layout="paged", kv_block_size=16,
        kv_pool_blocks=48, prefix_cache=True,
        kv_quantize=kv_quantize,
        prefix_store=_spec(
            tmp_path, **{"t1-bytes": 1, **overrides}
        ),
    )
    return ServingConfig(**base)


async def _drain_tiers(engine, timeout_s=15.0):
    """Wait until the demotion cascade fully reaches T2."""
    for _ in range(int(timeout_s / 0.02)):
        st = engine.stats()["prefixstore"]
        if (
            st["t0"]["blocks"] == 0
            and st["t1"]["entries"] == 0
            and not st["t2"]["in_transit_bytes"]
            and not st["t2"]["pending_jobs"]
        ):
            return st
        await asyncio.sleep(0.02)
    return engine.stats()["prefixstore"]


@pytest.mark.parametrize("kv_quantize", [None, "int8"])
def test_tier_roundtrip_byte_identity(tmp_path, kv_quantize):
    """Greedy tokens+text served from a T1-promoted and a T2-hydrated
    prefix are identical to a cold-computed run (f32; fp32 AND int8
    paged pools — int8 rows travel verbatim, bit-exact in transit)."""
    from langstream_tpu.serving.engine import TpuServingEngine

    prompt = list(range(1, 100))
    opts = {"max-tokens": 8, "temperature": 0}

    async def main():
        # cold reference: NO prefix store at all (pre-tier engine)
        from langstream_tpu.serving.engine import ServingConfig

        ref = TpuServingEngine(ServingConfig(
            model="tiny", slots=2, max_seq_len=256, decode_chunk=4,
            model_dtype="float32", kv_layout="paged", kv_block_size=16,
            kv_pool_blocks=48, prefix_cache=True, kv_quantize=kv_quantize,
        ))
        cold = await ref.generate(prompt, dict(opts))
        assert "prefixstore" not in ref.stats()
        await ref.close()

        # replica A: serves once (registers + demotes through the tiers)
        a = TpuServingEngine(_engine_config(tmp_path, kv_quantize))
        first = await a.generate(prompt, dict(opts))
        assert first["tokens"] == cold["tokens"]
        await _drain_tiers(a)
        # second request on A promotes from T1/T2 — byte-identical
        warm = await a.generate(prompt, dict(opts))
        assert warm["tokens"] == cold["tokens"]
        assert warm["text"] == cold["text"]
        st_a = a.stats()["prefixstore"]
        assert st_a["promotions"] >= 1
        assert st_a["demotions_t0_t1"] >= 1
        events = [e.get("kind") for e in a.flight.recent_events()]
        assert "prefix-demote" in events and "prefix-promote" in events
        await a.close()
        TpuServingEngine.reset_instances()

        # replica B: fresh engine, shared T2 only — hydrates, identical
        b = TpuServingEngine(_engine_config(tmp_path, kv_quantize))
        assert b.prefix_store.flush(10)
        hydrated = await b.generate(prompt, dict(opts))
        assert hydrated["tokens"] == cold["tokens"]
        assert hydrated["text"] == cold["text"]
        st_b = b.stats()["prefixstore"]
        assert st_b["hydrations"] > 0
        assert st_b["t1"]["hits"] > 0
        assert b.prefix_hits >= 1 and b.prefix_tokens > 0
        await b.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


def test_hydration_journey_segment(tmp_path):
    """A hydrated admission records hydrate-begin/hydrate-done journey
    edges that segment into ``prefix-hydrate``."""
    from langstream_tpu.serving.engine import TpuServingEngine
    from langstream_tpu.serving.journey import JOURNEYS, segments

    prompt = list(range(1, 100))

    async def main():
        a = TpuServingEngine(_engine_config(tmp_path))
        await a.generate(prompt, {"max-tokens": 4, "temperature": 0})
        await _drain_tiers(a)
        await a.close()
        TpuServingEngine.reset_instances()

        b = TpuServingEngine(_engine_config(tmp_path))
        assert b.prefix_store.flush(10)
        JOURNEYS.clear()
        await b.generate(prompt, {"max-tokens": 4, "temperature": 0})
        names = {
            seg["segment"]
            for jid in JOURNEYS.ids()
            for seg in segments(JOURNEYS.events(jid))
        }
        assert "prefix-hydrate" in names, names
        await b.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


def test_hydrate_timeout_falls_back_to_cold_compute(tmp_path):
    """A hydration whose blobs never arrive must not strand the request:
    the stash times out and the request cold-computes."""
    from langstream_tpu.serving.engine import TpuServingEngine

    prompt = list(range(1, 100))

    async def main():
        a = TpuServingEngine(_engine_config(tmp_path))
        cold = await a.generate(prompt, {"max-tokens": 4, "temperature": 0})
        await _drain_tiers(a)
        await a.close()
        TpuServingEngine.reset_instances()

        b = TpuServingEngine(
            _engine_config(tmp_path, **{"hydrate-timeout-s": 0.3})
        )
        assert b.prefix_store.flush(10)
        b.prefix_store.apply_results()
        # sabotage: the hydrator can never deliver (jobs pile up against
        # a dead queue) — drop the thread's job feed reference
        b.prefix_store._jobs.append(("stop",))
        b.prefix_store._kick.set()
        result = await asyncio.wait_for(
            b.generate(prompt, {"max-tokens": 4, "temperature": 0}), 30
        )
        assert result["tokens"] == cold["tokens"]
        events = [
            e for e in b.flight.recent_events()
            if e.get("kind") == "prefix-hydrate"
        ]
        assert any(e.get("stage") == "timeout" for e in events)
        await b.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


# --------------------------------------------------------------------------
# chaos: eviction storm + mid-hydration drain, ledger invariant
# --------------------------------------------------------------------------


def test_chaos_eviction_storm_and_drain_ledgers_exact(tmp_path):
    """Injected eviction storms (distinct prompts against tiny budgets
    under pool pressure) plus a drain landing mid-hydration leave the
    ledgers exactly summing: every byte resident, taken, or evicted
    with a counted reason — zero silent block loss."""
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        a = TpuServingEngine(
            _engine_config(tmp_path, **{"t2-bytes": 24 * 1024})
        )
        rng = random.Random(3)
        # storm: many distinct prompts churn T0 (budget 0) → T1 (1 byte)
        # → T2 (budget-trimmed), with organic pool-pressure evictions
        for i in range(8):
            base = rng.randrange(1, 200)
            prompt = [((base + j) % 250) + 1 for j in range(90)]
            await a.generate(prompt, {"max-tokens": 4, "temperature": 0})
        await _drain_tiers(a)
        st = a.stats()["prefixstore"]
        ledger = st["ledger"]
        resident = (
            ledger["t1_bytes"]
            + ledger["in_transit_bytes"]
            + ledger["t2_bytes"]
        )
        flows = (
            ledger["inserted_bytes"]
            + ledger["discovered_bytes"]
            - ledger["taken_bytes"]
            - ledger["evicted_bytes"]
        )
        assert resident == flows, ledger
        assert st["demotions_t0_t1"] > 0 and st["demotions_t1_t2"] > 0
        assert st["evictions"] > 0  # the t2 budget genuinely trimmed
        # the HBM ledger's prefix sub-owner agrees with the block manager
        memory = a.stats()["attribution"]["memory"]
        assert memory["kv_pool_prefix_bytes"] == (
            a.block_mgr.prefix_block_count() * a._kv_block_bytes
        )
        await a.close()
        TpuServingEngine.reset_instances()

        # drain lands while a hydration is stashed: the request must
        # complete (cold compute) inside the grace, ledgers still exact
        b = TpuServingEngine(_engine_config(tmp_path))
        assert b.prefix_store.flush(10)
        prompt = [((3 + j) % 250) + 1 for j in range(90)]
        task = asyncio.ensure_future(
            b.generate(prompt, {"max-tokens": 4, "temperature": 0})
        )
        # give admission a beat to stash the hydration, then drain
        await asyncio.sleep(0.05)
        report = await b.drain(grace_s=20.0)
        result = await asyncio.wait_for(task, 30)
        assert result["tokens"]  # completed, not lost
        assert report["shed"] == 0
        assert not b._prefix_hydrating
        ledger = b.prefix_store.ledger()
        resident = (
            ledger["t1_bytes"]
            + ledger["in_transit_bytes"]
            + ledger["t2_bytes"]
        )
        flows = (
            ledger["inserted_bytes"]
            + ledger["discovered_bytes"]
            - ledger["taken_bytes"]
            - ledger["evicted_bytes"]
        )
        assert resident == flows, ledger
        await b.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


# --------------------------------------------------------------------------
# acceptance e2e: the warm-prefix bench phase across 2 replicas
# --------------------------------------------------------------------------


def test_warm_prefix_bench_phase(tmp_path):
    """The acceptance criterion end to end: N tenants share one system
    prompt across 2 replicas; replica B's first shared-prefix request
    hydrates from T1/T2 (tier hits recorded in the bench JSON, a
    ``prefix-hydrate`` journey segment present) with TTFT below its
    cold-compute baseline, and prefix-affinity routing records
    ``prefix_hits`` > 0 with repeat traffic following the pin."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    from gateway_bench import run_warm_prefix_phase

    out = asyncio.run(
        run_warm_prefix_phase(
            tenants=3, repeats=2, max_tokens=4,
            t2_dir=str(tmp_path),
            serving={"max-seq-len": 1024, "slots": 2, "decode-chunk": 4},
        )
    )
    # tier hits recorded in the bench JSON
    assert out["tier_hits"]["t2_hydrations_b"] > 0
    assert out["tier_hits"]["t1_promotions_b"] > 0
    assert out["replica_a"]["t2_entries"] > 0
    # the journey's prefix-hydrate segment is present
    assert "prefix-hydrate" in (out.get("journey_segments") or {})
    # hydrated TTFT beats the same replica's cold-compute baseline
    assert out["prefix_hydrate_ttft_s"] < out["cold_compute_ttft_s"], out
    # prefix-affinity routing: repeat traffic landed on the holder
    assert out["router"]["prefix_hits"] > 0
    assert out["router"]["repeat_followed_pin"] is True
    # warm-phase repeats on A were served from the tiers
    assert out["tier_hits"]["t0_warm_hits"] > 0
