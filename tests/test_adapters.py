"""Multi-LoRA adapter serving e2e (serving/adapters.py, docs/ADAPTERS.md).

Layers covered: the spec (kebab round trip + deploy-time validation
rejects), the wire format (LSKV adapter blobs: kind/name/fingerprint/
factor-set checks), the store's tier mechanics (T0 row LRU + pin
refusal, T1 budget demote-vs-evict, T2 scan discovery + hydration +
the hydrate-pin window, fingerprint refusal-and-delete), the exact-
ledger property test (byte conservation across any install/demote/
hydrate/evict sequence), the engine integration (single-adapter greedy
f32 generation identical to offline-merged ``W + A @ B`` weights;
adapter-less and default-config surfaces byte-identical to the seed;
unknown-adapter and hydrate-timeout cold refusals; the journey's
``adapter-hydrate`` segment), the chaos leg (more adapters than T0
rows under concurrent mixed-adapter traffic — the evict/re-hydrate
storm completes every request with zero silent loss and exactly-
summing ledgers, and a fresh replica cold-starts from T2 byte-
identically to a locally-loaded run), the router's adapter affinity,
the gateway's tenant-config adapter stamp, the incident plane's
``adapter-storm`` thrash predicate, the engine_top adapters panel +
thrash flag, and the ``multi_lora`` bench phase.
"""

import asyncio
import importlib.util
import random
from pathlib import Path

import numpy as np
import pytest

from langstream_tpu.serving.adapters import (
    ADAPTER_HEADER,
    FACTOR_KEYS,
    AdapterStore,
    AdapterStoreSpec,
    AdapterUnavailable,
    check_adapter_name,
    deserialize_adapter,
    make_lora_arrays,
    merge_adapter_into_params,
    publish_adapter,
    serialize_adapter,
    validate_application_adapter_store,
)
from langstream_tpu.serving.kvtransfer import LayoutMismatch

FINGERPRINT = {
    "model": "tiny",
    "dtype": "float32",
    "rank": 2,
    "layers": 1,
    "hidden": 8,
    "heads": 2,
    "kv-heads": 1,
    "head-dim": 4,
}


def _spec(tmp_path=None, **overrides) -> AdapterStoreSpec:
    d = {
        "rank": 2,
        "t0-entries": 2,
        "t1-bytes": 1 << 20,
        "hydrate-timeout-s": 5.0,
        "t2-rescan-s": 0.1,
    }
    if tmp_path is not None:
        d["t2"] = {"type": "local", "path": str(tmp_path)}
    d.update(overrides)
    return AdapterStoreSpec.from_dict(d)


def _store(tmp_path=None, clock=None, **overrides) -> AdapterStore:
    kwargs = {} if clock is None else {"clock": clock}
    return AdapterStore(
        _spec(tmp_path, **overrides),
        fingerprint=dict(FINGERPRINT),
        entry_bytes=4096,
        **kwargs,
    )


def _arrays(seed: int) -> dict[str, np.ndarray]:
    """Tiny factor set matching FINGERPRINT (one layer, rank 2)."""
    return make_lora_arrays(
        layers=1, hidden=8, heads=2, kv_heads=1, head_dim=4,
        rank=2, seed=seed,
    )


def _nbytes(arrays: dict[str, np.ndarray]) -> int:
    return int(sum(a.nbytes for a in arrays.values()))


def _assert_conserved(store: AdapterStore) -> None:
    ledger = store.ledger()
    resident = (
        ledger["t1_bytes"]
        + ledger["in_transit_bytes"]
        + ledger["t2_bytes"]
    )
    flows = (
        ledger["inserted_bytes"]
        + ledger["discovered_bytes"]
        - ledger["evicted_bytes"]
    )
    assert resident == flows, ledger


def _settle(store: AdapterStore, timeout_s: float = 10.0) -> None:
    """Flush the hydrator and apply its results (tests only)."""
    assert store.flush(timeout_s)
    store.apply_results()


# --------------------------------------------------------------------------
# spec + validation + names
# --------------------------------------------------------------------------


def test_spec_roundtrip_and_defaults():
    spec = _spec()
    back = AdapterStoreSpec.from_dict(spec.to_dict())
    assert back == spec
    assert AdapterStoreSpec.from_dict(None) is None
    full = AdapterStoreSpec.from_dict(
        {
            "enabled": True,
            "rank": 16,
            "t0-entries": 8,
            "t1-bytes": 4096,
            "t2-bytes": 1 << 30,
            "t2": {"type": "local", "path": "/tmp/x"},
            "hydrate-timeout-s": 2.5,
            "t2-rescan-s": 1.0,
        }
    )
    assert AdapterStoreSpec.from_dict(full.to_dict()) == full
    assert full.t2_config() == {"type": "local", "path": "/tmp/x"}
    # defaults
    bare = AdapterStoreSpec.from_dict({})
    assert bare.rank == 8 and bare.t0_entries == 4
    assert bare.hydrate_timeout_s == 5.0 and bare.t2_config() is None


@pytest.mark.parametrize(
    "bad",
    [
        {"rank": 0},
        {"t0-entries": 0},
        {"t1-bytes": 0},
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
        AdapterStoreSpec.from_dict(bad)


def test_validate_application_adapter_store():
    class Res:
        type = "tpu-serving-configuration"

        def __init__(self, conf):
            self.configuration = conf

    class App:
        def __init__(self, conf):
            self.resources = {"tpu": Res(conf)}

    validate_application_adapter_store(App({"adapter-store": None}))
    validate_application_adapter_store(
        App({"adapter-store": {"rank": 4, "t0-entries": 2}})
    )
    with pytest.raises(ValueError, match="adapter-store"):
        validate_application_adapter_store(
            App({"adapter-store": {"rank": -1}})
        )


def test_check_adapter_name():
    assert check_adapter_name("tenant-a-v3") == "tenant-a-v3"
    for bad in ("", "a/b", "a b", "a\nb", "x" * 121, None):
        with pytest.raises(ValueError):
            check_adapter_name(bad)


# --------------------------------------------------------------------------
# wire format
# --------------------------------------------------------------------------


def test_wire_roundtrip_and_checks():
    arrays = _arrays(1)
    blob = serialize_adapter("a1", arrays, FINGERPRINT)
    back = deserialize_adapter(blob, "a1", FINGERPRINT)
    assert set(back) == set(FACTOR_KEYS)
    for k in FACTOR_KEYS:
        np.testing.assert_array_equal(back[k], arrays[k])
    # name-vs-key mismatch
    with pytest.raises(LayoutMismatch, match="does not match"):
        deserialize_adapter(blob, "a2", FINGERPRINT)
    # fingerprint mismatch names the disagreeing key
    with pytest.raises(LayoutMismatch, match="rank"):
        deserialize_adapter(blob, "a1", {**FINGERPRINT, "rank": 4})
    # missing factor
    partial = {k: v for k, v in arrays.items() if k != "wo_b"}
    bad = serialize_adapter("a1", partial, FINGERPRINT)
    with pytest.raises(LayoutMismatch, match="missing factors"):
        deserialize_adapter(bad, "a1", FINGERPRINT)


# --------------------------------------------------------------------------
# store tier mechanics
# --------------------------------------------------------------------------


def test_t0_row_lru_pin_and_refusal():
    store = _store()  # 2 device rows, no T2
    for name, seed in (("a", 1), ("b", 2), ("c", 3)):
        store.install(name, _arrays(seed))
    ra = store.t0_assign("a")
    rb = store.t0_assign("b")
    assert {ra, rb} == {1, 2}  # row 0 is the reserved zeros row
    # LRU bump: touching "a" makes "b" the eviction victim
    assert store.t0_row("a") == ra
    rc = store.t0_assign("c")
    assert rc == rb
    assert store.t0_evictions == 1
    assert sorted(store.t0_resident()) == ["a", "c"]
    # pins refuse eviction: with both rows pinned a new assign fails
    store.pin("a")
    store.pin("c")
    assert store.t0_assign("b") is None
    assert store.eviction_refusals == 1
    # releasing one pin unblocks the assignment
    store.unpin("c")
    assert store.t0_assign("b") == rc
    assert store.pinned("a") == 1 and store.pinned("c") == 0
    kinds = [k for k, _ in store.drain_events()]
    assert kinds.count("adapter-evict") == 2
    _assert_conserved(store)


def test_t1_budget_evicts_without_t2_demotes_with(tmp_path):
    arrays = _arrays(1)
    per = _nbytes(arrays)
    # no T2: the second install pushes the first out — counted eviction
    store = _store(**{"t1-bytes": per + per // 2})
    store.install("a", _arrays(1))
    store.install("b", _arrays(2))
    assert store.t1_has("b") and not store.t1_has("a")
    assert store.evictions == 1 and store.evicted_bytes == per
    events = store.drain_events()
    assert ("adapter-evict", {
        "tier": "t1", "adapter": "a", "bytes": per, "reason": "t1-budget",
    }) in events
    _assert_conserved(store)

    # with T2: the overflow demotes instead — bytes move through
    # in_transit into the T2 index, nothing is lost
    store2 = _store(tmp_path, **{"t1-bytes": per + per // 2})
    store2.install("a", _arrays(1))
    store2.install("b", _arrays(2))
    _settle(store2)
    assert store2.demotions_t1_t2 == 1
    assert store2.t2_has("a") and store2.t2_bytes == per
    assert store2.in_transit_bytes == 0
    assert store2.evictions == 0
    _assert_conserved(store2)
    store2.close()


def test_t2_scan_discovery_and_hydration(tmp_path):
    publish_adapter(
        {"type": "local", "path": str(tmp_path)},
        "pub", _arrays(9), FINGERPRINT,
    )
    store = _store(tmp_path)
    _settle(store)  # initial scan job
    assert store.known("pub") and store.t2_has("pub")
    ledger = store.ledger()
    # discovered via scan: size unknown until first fetch
    assert ledger["t2_bytes"] == 0 and ledger["discovered_bytes"] == 0
    assert store.request_hydration(["pub"]) == 1
    _settle(store)
    assert store.t1_has("pub")
    per = _nbytes(_arrays(9))
    ledger = store.ledger()
    assert ledger["discovered_bytes"] == per
    assert ledger["t2_bytes"] == per  # still durable in T2
    assert store.hydrations == 1 and store.t2_hits == 1
    kinds = [k for k, _ in store.drain_events()]
    assert "adapter-hydrate" in kinds
    _assert_conserved(store)
    # unknown names are nothing to wait for
    assert store.request_hydration(["nope"]) == 0
    store.close()


def test_hydrated_entries_pinned_against_shrink(tmp_path):
    """A freshly hydrated T1 entry survives the budget shrink for one
    hydrate-timeout window (no hydrate->evict->re-hydrate livelock);
    the pin expires with the fake clock and the shrink proceeds."""
    now = [1000.0]
    per = _nbytes(_arrays(1))
    store = _store(
        tmp_path, clock=lambda: now[0],
        **{"t1-bytes": per + per // 2, "hydrate-timeout-s": 5.0},
    )
    publish_adapter(
        {"type": "local", "path": str(tmp_path)},
        "hyd", _arrays(3), FINGERPRINT,
    )
    store._jobs.append(("scan",))
    store._kick.set()
    _settle(store)
    store.request_hydration(["hyd"])
    _settle(store)
    assert store.t1_has("hyd")
    # a local install overflows the budget — but the hydrated entry is
    # pin-protected, so the INSTALL itself is the eviction victim...
    store.install("loc", _arrays(4))
    assert store.t1_has("hyd")
    # ...until the window passes: then the hydrated entry shrinks away
    now[0] += 6.0
    store.install("loc2", _arrays(5))
    store._shrink_t1()
    assert not store.t1_has("hyd")
    _settle(store)
    _assert_conserved(store)
    store.close()


def test_fingerprint_mismatch_refused_and_deleted(tmp_path):
    publish_adapter(
        {"type": "local", "path": str(tmp_path)},
        "bad", _arrays(2), {**FINGERPRINT, "rank": 64},
    )
    store = _store(tmp_path)
    _settle(store)
    assert store.t2_has("bad")
    store.request_hydration(["bad"])
    _settle(store)
    assert not store.t1_has("bad")
    assert store.fingerprint_refusals == 1
    assert store.hydrate_failures == 1
    assert not store.t2_has("bad")  # dropped from the index
    events = store.drain_events()
    refusal = [
        d for k, d in events
        if k == "adapter-evict" and "fingerprint" in d.get("reason", "")
    ]
    assert refusal and refusal[0]["adapter"] == "bad"
    # the blob was DELETED from the origin: the next scan cannot
    # resurrect a blob that would refuse forever
    store._jobs.append(("scan",))
    store._kick.set()
    _settle(store)
    assert not store.known("bad")
    _assert_conserved(store)
    store.close()


def test_t2_byte_budget_trims_oldest(tmp_path):
    per = _nbytes(_arrays(1))
    store = _store(
        tmp_path,
        **{"t1-bytes": per + per // 2, "t2-bytes": per + per // 2},
    )
    for name, seed in (("a", 1), ("b", 2), ("c", 3)):
        store.install(name, _arrays(seed))
        _settle(store)
    # two demotions landed; the T2 budget holds one — oldest trimmed
    assert store.demotions_t1_t2 == 2
    assert store.t2_bytes <= per + per // 2
    assert store.evictions >= 1
    trims = [
        d for k, d in store.drain_events()
        if k == "adapter-evict" and d.get("reason") == "t2-budget"
    ]
    assert trims
    _assert_conserved(store)
    store.close()


# --------------------------------------------------------------------------
# ledger conservation property
# --------------------------------------------------------------------------


def test_ledger_conservation_property(tmp_path):
    """Random install/assign/hydrate/shrink/trim sequences keep
    ``t1 + in_transit + t2 == inserted + discovered - evicted`` exact
    at every settle point."""
    rng = random.Random(7)
    per = _nbytes(_arrays(0))
    store = _store(
        tmp_path,
        **{"t1-bytes": int(per * 2.5), "t2-bytes": per * 3},
    )
    names = [f"ad-{i}" for i in range(8)]
    # seed a couple of T2-only blobs for scan discovery
    for i in (6, 7):
        publish_adapter(
            {"type": "local", "path": str(tmp_path)},
            names[i], _arrays(100 + i), FINGERPRINT,
        )
    store._jobs.append(("scan",))
    store._kick.set()
    for step in range(60):
        op = rng.randrange(5)
        name = rng.choice(names)
        if op == 0:
            store.install(name, _arrays(hash(name) % 997))
        elif op == 1:
            store.t0_assign(name)
        elif op == 2:
            store.request_hydration([name])
        elif op == 3:
            store.pin(name) if rng.random() < 0.5 else store.unpin(name)
        else:
            _settle(store)
            _assert_conserved(store)
    _settle(store)
    _assert_conserved(store)
    # T0's copy-tier ledger is exact arithmetic over the row map
    assert store.ledger()["t0_bytes"] == len(store.t0_resident()) * 4096
    store.drain_events()
    store.close()


# --------------------------------------------------------------------------
# engine integration: merge pin, byte-identity, refusals, journey
# --------------------------------------------------------------------------

TINY = dict(
    model="tiny", slots=2, max_seq_len=256, decode_chunk=4,
    model_dtype="float32", kv_layout="paged", kv_block_size=16,
    kv_pool_blocks=48,
)


def _engine_config(tmp_path=None, **overrides):
    from langstream_tpu.serving.engine import ServingConfig

    spec = {
        "rank": 4,
        "t0-entries": 2,
        "t1-bytes": 8 << 20,
        "hydrate-timeout-s": 10.0,
        "t2-rescan-s": 0.1,
    }
    if tmp_path is not None:
        spec["t2"] = {"type": "local", "path": str(tmp_path)}
    spec.update(overrides)
    return ServingConfig(
        **TINY, adapter_store=AdapterStoreSpec.from_dict(spec)
    )


def _engine_arrays(seed: int) -> dict[str, np.ndarray]:
    """Factors matching the tiny model at the engine specs' rank 4."""
    return make_lora_arrays(
        layers=2, hidden=64, heads=4, kv_heads=2, head_dim=16,
        rank=4, seed=seed,
    )


def test_single_adapter_matches_offline_merge():
    """The correctness pin: greedy f32 generation through the ragged
    batched adapter path equals the base model with the same deltas
    merged offline (``W + A @ B``), and so do the logits it is drawn from;
    that the adapter steers the model is read off the logits too (a random
    rank-4 delta of scale 0.02 need not change eight greedy tokens)."""
    import jax.numpy as jnp

    from langstream_tpu.models.llama import prefill_forward
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    prompt = list(range(1, 80))
    opts = {"max-tokens": 8, "temperature": 0}
    arrays = _engine_arrays(11)

    async def main():
        a = TpuServingEngine(_engine_config())
        a.install_adapter("tenant-a-v1", arrays)
        adapted = await a.generate(
            prompt, {**opts, "adapter": "tenant-a-v1"}
        )
        base = await a.generate(prompt, dict(opts))
        st = a.stats()["adapters"]
        assert st["t0"]["loads"] == 1
        assert sorted(st["t0"]["resident"]) == ["tenant-a-v1"]
        # the logits of the prompt's last position, from the device rows
        # the served programs read: adapter row, no adapter, merged weights
        row = next(
            r for r in range(1, a._ad_layers["wq_a"].shape[1])
            if float(jnp.abs(a._ad_layers["wq_a"][:, r]).max()) > 0
        )
        forward = lambda params, adapters=None: np.asarray(  # noqa: E731
            prefill_forward(
                a.model_config, params, jnp.asarray([prompt], jnp.int32),
                jnp.asarray([len(prompt)]), False, adapters=adapters,
            )[0]
        )
        adapted_logits = forward(
            a.params,
            {"ids": jnp.asarray([row], jnp.int32), "layers": a._ad_layers},
        )
        base_logits = forward(a.params)
        merged_logits = forward(merge_adapter_into_params(a.params, arrays))
        await a.close()
        TpuServingEngine.reset_instances()

        # offline-merged reference: a store-less engine whose attention
        # weights carry the deltas
        ref = TpuServingEngine(ServingConfig(**TINY))
        ref.params = merge_adapter_into_params(ref.params, arrays)
        merged = await ref.generate(prompt, dict(opts))
        plain = await ref.generate(prompt, dict(opts))  # merged != base
        await ref.close()
        TpuServingEngine.reset_instances()

        assert adapted["tokens"] == merged["tokens"]
        assert adapted["text"] == merged["text"]
        assert merged["tokens"] == plain["tokens"]  # determinism sanity
        assert len(base["tokens"]) == len(adapted["tokens"])
        off_merge = np.abs(adapted_logits - merged_logits).max()
        steered = np.abs(adapted_logits - base_logits).max()
        assert off_merge < 1e-4, off_merge
        # the adapter genuinely steered the model
        assert steered > 100 * max(off_merge, 1e-6), (steered, off_merge)

    asyncio.run(main())


def test_adapterless_surfaces_byte_identical_to_seed():
    """Adapter-less traffic on an adapter-enabled engine produces the
    seed's exact tokens, and a default-config engine exposes no adapter
    surface anywhere (stats, scrape)."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    prompt = list(range(1, 80))
    opts = {"max-tokens": 8, "temperature": 0}

    async def main():
        seed = TpuServingEngine(ServingConfig(**TINY))
        want = await seed.generate(prompt, dict(opts))
        stats = seed.stats()
        assert "adapters" not in stats
        assert seed.adapter_store is None and seed._ad_layers is None
        assert not any(
            str(e.get("kind", "")).startswith("adapter")
            for e in seed.flight.recent_events()
        )
        await seed.close()
        TpuServingEngine.reset_instances()

        with_store = TpuServingEngine(_engine_config())
        with_store.install_adapter("unused", _engine_arrays(5))
        got = await with_store.generate(prompt, dict(opts))
        assert got["tokens"] == want["tokens"]
        assert got["text"] == want["text"]
        events = [
            e["kind"] for e in with_store.flight.recent_events()
        ]
        assert "adapter-load" not in events  # nothing resolved a row
        await with_store.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


def test_unknown_adapter_refused_cold():
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        a = TpuServingEngine(_engine_config())
        with pytest.raises(AdapterUnavailable, match="not resident"):
            await a.generate(
                list(range(1, 40)),
                {"max-tokens": 4, "temperature": 0, "adapter": "ghost"},
            )
        st = a.stats()["adapters"]
        assert st["refusals"] == 1
        events = [e["kind"] for e in a.flight.recent_events()]
        assert "adapter-refused" in events
        await a.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


def test_install_adapter_shape_checked():
    from langstream_tpu.serving.engine import TpuServingEngine

    a = TpuServingEngine(_engine_config())
    wrong_rank = make_lora_arrays(
        layers=2, hidden=64, heads=4, kv_heads=2, head_dim=16,
        rank=2, seed=1,
    )
    with pytest.raises(ValueError, match="shape"):
        a.install_adapter("bad", wrong_rank)

    async def main():
        await a.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


def test_hydrate_timeout_refuses_cold(tmp_path):
    """A hydration whose blob never arrives refuses the request loudly
    inside the deadline — never a silent strand, never a silent base-
    weights answer."""
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        publish_adapter(
            {"type": "local", "path": str(tmp_path)},
            "slow", _engine_arrays(3), FINGERPRINT,  # wrong fp is fine:
        )  # the fetch never happens — the hydrator dies first
        b = TpuServingEngine(
            _engine_config(tmp_path, **{"hydrate-timeout-s": 0.3})
        )
        store = b.adapter_store
        assert store.flush(10)
        store.apply_results()
        assert store.t2_has("slow")
        # sabotage: the hydrator thread exits — fetches never complete
        store._jobs.append(("stop",))
        store._kick.set()
        with pytest.raises(AdapterUnavailable, match="timed out"):
            await asyncio.wait_for(
                b.generate(
                    list(range(1, 40)),
                    {"max-tokens": 4, "temperature": 0, "adapter": "slow"},
                ),
                30,
            )
        events = [
            e for e in b.flight.recent_events()
            if e.get("kind") == "adapter-hydrate"
        ]
        assert any(e.get("stage") == "timeout" for e in events)
        assert not b._adapter_hydrating
        await b.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


def test_hydration_journey_segment(tmp_path):
    """A T2 cold-start admission records adapter-hydrate journey edges
    that segment into ``adapter-hydrate``."""
    from langstream_tpu.serving.engine import TpuServingEngine
    from langstream_tpu.serving.journey import JOURNEYS, segments

    async def main():
        eng = TpuServingEngine(_engine_config(tmp_path))
        publish_adapter(
            {"type": "local", "path": str(tmp_path)},
            "pub", _engine_arrays(2), eng.adapter_fingerprint(),
        )
        store = eng.adapter_store
        for _ in range(200):
            store.apply_results()
            if store.t2_has("pub"):
                break
            await asyncio.sleep(0.02)
        assert store.t2_has("pub")
        JOURNEYS.clear()
        out = await eng.generate(
            list(range(1, 80)),
            {"max-tokens": 4, "temperature": 0, "adapter": "pub"},
        )
        assert out["tokens"]
        segs = set()
        for jid in JOURNEYS.ids():
            for s in segments(JOURNEYS.events(jid)):
                segs.add(s["segment"])
        assert "adapter-hydrate" in segs
        st = eng.stats()["adapters"]
        assert st["hydrations"] >= 1
        await eng.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


# --------------------------------------------------------------------------
# chaos: mixed-adapter eviction storm + cross-replica T2 cold start
# --------------------------------------------------------------------------


def test_chaos_eviction_storm_zero_silent_loss(tmp_path):
    """More adapters than T0 rows under concurrent mixed-adapter
    traffic: the evict/re-hydrate storm completes every request, the
    per-tier ledgers sum exactly, and a fresh replica serving from the
    shared T2 origin answers byte-identically to a locally-loaded
    run."""
    from langstream_tpu.serving.engine import TpuServingEngine

    per = _nbytes(_engine_arrays(0))
    names = [f"ad-{i}" for i in range(4)]
    prompt = list(range(1, 60))
    opts = {"max-tokens": 4, "temperature": 0}

    async def main():
        # T0 holds 2 rows, T1 holds ~2 adapters: 4 adapters churn both
        a = TpuServingEngine(
            _engine_config(
                tmp_path,
                **{"t0-entries": 2, "t1-bytes": int(per * 2.5)},
            )
        )
        for i in (0, 1):
            a.install_adapter(names[i], _engine_arrays(i))
        for i in (2, 3):
            publish_adapter(
                {"type": "local", "path": str(tmp_path)},
                names[i], _engine_arrays(i), a.adapter_fingerprint(),
            )
        store = a.adapter_store
        for _ in range(400):
            store.apply_results()
            if all(store.known(n) for n in names):
                break
            await asyncio.sleep(0.02)
        assert all(store.known(n) for n in names)

        submitted, results = 0, []
        for wave in range(3):
            batch = []
            for i, name in enumerate(names):
                o = dict(opts)
                if i % 2 == 0 or wave == 0:
                    o["adapter"] = name
                # odd slots in later waves ride base weights: the mixed
                # batch is the point of the ragged gather
                batch.append(a.generate(list(prompt), o))
                submitted += 1
            results.extend(
                await asyncio.gather(*batch, return_exceptions=True)
            )
        failures = [r for r in results if isinstance(r, BaseException)]
        completions = [r for r in results if not isinstance(r, BaseException)]
        # zero silent loss: every submission either completed or raised
        assert len(completions) + len(failures) == submitted
        assert not failures, failures
        assert all(r["tokens"] for r in completions)

        st = a.stats()["adapters"]
        # the storm genuinely churned the tiers
        assert st["t0"]["evictions"] + st["evictions"] > 0
        assert st["hydrations"] >= 1
        _assert_conserved(store)
        assert st["t0"]["bytes"] == st["t0"]["entries"] * st["entry_bytes"]
        kinds = [e["kind"] for e in a.flight.recent_events()]
        assert "adapter-load" in kinds and "adapter-evict" in kinds

        # the locally-loaded reference answer for the cold-start pin
        ref = await a.generate(
            list(prompt), {**opts, "adapter": names[2]}
        )
        await a.close()
        TpuServingEngine.reset_instances()

        # replica B: fresh engine, shared T2 only — discovers, hydrates,
        # and serves the SAME adapter byte-identically
        b = TpuServingEngine(_engine_config(tmp_path))
        store_b = b.adapter_store
        for _ in range(400):
            store_b.apply_results()
            if store_b.t2_has(names[2]):
                break
            await asyncio.sleep(0.02)
        cold = await b.generate(
            list(prompt), {**opts, "adapter": names[2]}
        )
        assert cold["tokens"] == ref["tokens"]
        assert cold["text"] == ref["text"]
        st_b = b.stats()["adapters"]
        assert st_b["hydrations"] >= 1
        _assert_conserved(store_b)
        await b.close()
        TpuServingEngine.reset_instances()

    asyncio.run(main())


# --------------------------------------------------------------------------
# router affinity + gateway stamp
# --------------------------------------------------------------------------


def test_router_adapter_affinity():
    from langstream_tpu.gateway.router import ReplicaRouter

    r = ReplicaRouter()
    r.observe([
        {"replica": "app-ai-0", "queued": 0, "occupancy": 0, "slots": 4},
        {"replica": "app-ai-1", "queued": 5, "occupancy": 4, "slots": 4},
    ])
    assert r.pick("t1", adapter="tenant-a-v1") == "app-ai-0"
    # load inverts: the adapter pin holds — even for a different tenant
    r.observe([
        {"replica": "app-ai-0", "queued": 9, "occupancy": 4, "slots": 4},
        {"replica": "app-ai-1", "queued": 0, "occupancy": 0, "slots": 4},
    ])
    assert r.pick("t2", adapter="tenant-a-v1") == "app-ai-0"
    stats = r.stats()
    assert stats["adapter_hits"] == 1
    assert stats["pinned_adapters"] == 1
    # adapter-less traffic keeps the least-loaded choice
    assert r.pick("t3") == "app-ai-1"
    # the pinned replica drains: the pin breaks, traffic re-pins
    r.observe([
        {
            "replica": "app-ai-0", "queued": 0, "occupancy": 0,
            "slots": 4, "draining": True,
        },
        {"replica": "app-ai-1", "queued": 0, "occupancy": 0, "slots": 4},
    ])
    assert r.pick("t2", adapter="tenant-a-v1") == "app-ai-1"
    assert r.stats()["adapter_rerouted"] == 1
    assert r.pick("t9", adapter="tenant-a-v1") == "app-ai-1"
    assert r.stats()["adapter_hits"] == 2


def test_gateway_stamps_adapter_from_tenant_config():
    from langstream_tpu.gateway.server import GatewayServer
    from langstream_tpu.serving.qos import QosSpec, TenantLimiter

    server = GatewayServer(port=0)
    spec = QosSpec.from_dict({
        "tenants": {
            "acme": {"adapter": "acme-summarizer-v2"},
            "plain": {},
        },
    })
    limiter = TenantLimiter(spec)
    out = server._qos_headers(limiter, {"tenant": "acme"}, {})
    assert out[ADAPTER_HEADER] == "acme-summarizer-v2"
    # a tenant with no adapter configured stamps nothing extra
    out2 = server._qos_headers(limiter, {"tenant": "plain"}, {})
    assert ADAPTER_HEADER not in out2
    # no QoS at all: headers stay byte-identical to the seed
    assert server._qos_headers(None, {}, {}) == {}


def test_tenant_policy_adapter_roundtrip():
    from langstream_tpu.serving.qos import QosSpec

    spec = QosSpec.from_dict({
        "tenants": {"acme": {"adapter": "a-v1"}},
    })
    assert spec.tenant_policy("acme").adapter == "a-v1"
    d = spec.to_dict()
    assert d["tenants"]["acme"]["adapter"] == "a-v1"
    # empty adapter is omitted from the wire — pre-adapter configs
    # round-trip byte-identically
    bare = QosSpec.from_dict({"tenants": {"x": {}}})
    assert "adapter" not in bare.to_dict()["tenants"]["x"]


# --------------------------------------------------------------------------
# incident plane: the adapter-storm thrash predicate
# --------------------------------------------------------------------------


def test_adapter_eviction_storm_predicate():
    from langstream_tpu.serving.incident import (
        OFFENDING_SEGMENT,
        TRIGGER_KINDS,
        adapter_eviction_storm,
    )

    assert "adapter-storm" in TRIGGER_KINDS
    assert OFFENDING_SEGMENT["adapter-storm"] == "adapter-hydrate"

    def ev(adapter, m_s):
        return {"kind": "adapter-evict", "adapter": adapter, "m_s": m_s}

    now = 100.0
    # same adapter bouncing: thrash
    events = [ev("hot", now - 9), ev("hot", now - 5), ev("hot", now - 1)]
    hit = adapter_eviction_storm(events, now, k=3, window_s=30.0)
    assert hit == {
        "adapter": "hot", "count": 3, "window_s": 30.0,
        "evictions": events,
    }
    # distinct adapters cycling is healthy LRU turnover, not thrash
    churn = [ev("a", now - 9), ev("b", now - 5), ev("c", now - 1)]
    assert adapter_eviction_storm(churn, now, k=3, window_s=30.0) is None
    # old evictions age out of the window
    stale = [ev("hot", now - 90), ev("hot", now - 80), ev("hot", now - 1)]
    assert adapter_eviction_storm(stale, now, k=3, window_s=30.0) is None


# --------------------------------------------------------------------------
# engine_top: adapters panel + thrash flag
# --------------------------------------------------------------------------


def _load_engine_top():
    path = Path(__file__).resolve().parents[1] / "tools" / "engine_top.py"
    spec = importlib.util.spec_from_file_location("engine_top", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _adapters_entry(evict_t_ms):
    # the summary.totals block makes the entry flight-shaped so
    # analyze()'s dump walker collects it (anomaly flags ride there)
    return {
        "engine": "e0",
        "summary": {
            "totals": {"device_ms": 10.0, "host_ms": 1.0, "stall_ms": 0.0},
        },
        "adapters": {
            "t0": {
                "entries": 2, "budget_entries": 2,
                "bytes": 8192, "budget_bytes": 8192,
                "resident": ["ad-0", "ad-1"], "pinned": {"ad-0": 1},
                "hits": 6, "loads": 4, "evictions": len(evict_t_ms),
                "eviction_refusals": 1,
            },
            "t1": {
                "entries": 3, "bytes": 12288, "budget_bytes": 1 << 20,
                "hits": 5, "misses": 2,
            },
            "t2": {
                "enabled": True, "entries": 4, "bytes": 16384,
                "blob_bytes": 17000, "budget_bytes": None, "hits": 3,
                "in_transit_bytes": 0, "pending_jobs": 0, "scans": 9,
            },
            "rank": 4, "entry_bytes": 4096, "hydrate_timeout_s": 10.0,
            "installs": 2, "demotions_t1_t2": 1, "hydrations": 3,
            "hydrating": 0, "hydrate_failures": 0,
            "fingerprint_refusals": 0, "evictions": 2, "refusals": 1,
        },
        "events": [
            {
                "kind": "adapter-evict", "tier": "t0", "adapter": "ad-0",
                "bytes": 4096, "t_ms": t, "reason": "t0-capacity",
            }
            for t in evict_t_ms
        ],
    }


def test_engine_top_renders_adapters_panel():
    engine_top = _load_engine_top()
    frame = engine_top.render([_adapters_entry([1000.0])])
    assert "adapter" in frame
    assert "rows 2/2" in frame
    assert "ad-0(1)" in frame  # pin count in parens
    assert "refused cold 1" in frame
    # adapter-less payloads render with no adapter lines at all
    quiet = engine_top.render([{"engine": "e0"}])
    assert "adapter" not in quiet
    # --json mirrors the rendered panel
    payload = engine_top.render_json([_adapters_entry([1000.0])])[0]
    panel = payload["panels"]["adapters"]
    assert panel["section"]["rank"] == 4
    assert any("adapter" in ln for ln in panel["lines"])


def test_engine_top_analyze_flags_adapter_thrash():
    engine_top = _load_engine_top()
    # 3 evictions of ONE adapter inside the 10s hydrate window
    out = engine_top.analyze(
        [_adapters_entry([1000.0, 4000.0, 9000.0])]
    )
    assert "adapter thrash" in out and "'ad-0'" in out
    # spread past the window: quiet
    quiet = engine_top.analyze(
        [_adapters_entry([1000.0, 15000.0, 30000.0])]
    )
    assert "adapter thrash" not in quiet


# --------------------------------------------------------------------------
# acceptance e2e: the multi-LoRA bench phase
# --------------------------------------------------------------------------


def test_multi_lora_bench_phase(tmp_path):
    """The bench leg end to end: mixed-adapter traffic over an
    undersized T0 with half the adapters published T2-only — every
    request completes, the ledger balances, and the perf_diff metrics
    are all present."""
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    from gateway_bench import run_multi_lora_phase

    out = asyncio.run(
        run_multi_lora_phase(
            tenants=4, adapters=4, repeats=2, max_tokens=4,
            t2_dir=str(tmp_path),
        )
    )
    assert out["zero_silent_loss"] is True
    assert out["failures"] == []
    assert out["ledger_balanced"] is True
    assert out["multi_lora_evictions"] > 0  # the churn genuinely ran
    assert out["hydrations"] > 0  # the T2-published half hydrated
    assert 0.0 <= out["multi_lora_t0_hit_ratio"] <= 1.0
    assert out["multi_lora_ttft_p99_s"] > 0
    assert "adapter-hydrate" in (out.get("journey_segments") or {})
    assert out["router"]["adapter_hits"] > 0
    assert out["flight_events"].get("adapter-load", 0) > 0
