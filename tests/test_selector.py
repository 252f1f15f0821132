"""Incremental placement selection (repro.core.selector): the indexed
lazy-heap selector must reproduce the reference scan's decisions
move-for-move — on randomized controller histories (inserts, hits, run
signals, alpha changes, topology on/off) under repeated ``_enforce``
pressure — while the supporting machinery (per-tier entry index, top-k
candidate selection, SIMCHECK cross-check and sanitizer invariant)
holds up under fault injection."""
import heapq

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compression import default_registry
from repro.core.controller import AdaptCacheController
from repro.core.estimator import (
    DEFAULT_DECOMPRESS_BPS, DelayProfile, FrequencyEstimator,
    QualityEstimator,
)
from repro.core.policy import AdaptivePolicy, FixedPolicy
from repro.core.selector import (
    IndexedSelector, ScanSelector, SelectorMismatch, make_selector,
)
from repro.serving.sanitizer import SanitizerError, SimSanitizer
from repro.storage.tier import DRAMTier, DeviceSpec, SSDTier
from repro.storage.topology import StorageTopology


def make_kv(rng, T=128, L=2, F=64):
    return {"k": rng.randn(L, T, F).astype(np.float32),
            "v": rng.randn(L, T, F).astype(np.float32),
            "positions": np.arange(T, dtype=np.int32)}


def build(selector="indexed", policy="adaptive", alpha=0.01, dram_mb=1,
          ssd_mb=8, topology=None, tmp=None):
    methods = default_registry()
    topo = topology
    dram_names = topo.dram_names if topo is not None else ["dram"]
    tiers = {name: DRAMTier(DeviceSpec("dram", dram_mb << 20, 16e9, 16e9,
                                       20e-6), name=name)
             for name in dram_names}
    tiers["ssd"] = SSDTier(DeviceSpec("ssd", ssd_mb << 20, 1e9, 1e9, 1e-4),
                           root=tmp)
    order = topo.tier_names if topo is not None else ["dram", "ssd"]
    q = QualityEstimator()
    q.set_curve("qa", "kivi", [(0.09, 0.8), (0.16, 0.92), (0.28, 0.98)])
    q.set_curve("qa", "streaming_llm",
                [(0.125, 0.5), (0.25, 0.7), (0.5, 0.88), (1.0, 1.0)])
    q.set_curve("qa", "drop_kivi", [(0.02, 0.4), (0.05, 0.6), (0.14, 0.85)])
    f = FrequencyEstimator(halflife_s=600)
    dp = DelayProfile(dict(DEFAULT_DECOMPRESS_BPS))
    pol = (AdaptivePolicy(methods, tiers, order, q, f, dp, alpha=alpha,
                          topology=topo)
           if policy == "adaptive"
           else FixedPolicy(methods, order, *policy, topology=topo))
    clock = [0.0]
    return AdaptCacheController(methods, tiers, order, pol, dp, f,
                                clock=lambda: clock[0], topology=topo,
                                selector=selector), clock


# -- randomized decision-equivalence harness ---------------------------------

def gen_ops(rng, n_ops=60, paged=False, replicas=1):
    """A randomized controller history: clock ticks, inserts (over-
    capacity -> repeated ``_enforce`` pressure), hits, page-run signals
    and mid-run alpha changes. KV arrays are materialized HERE so both
    replays see byte-identical inputs."""
    ops, keys = [], []
    for i in range(n_ops):
        ops.append(("tick", float(rng.rand() * 2.0)))
        r = rng.rand()
        if r < 0.45 or not keys:
            key = (f"pg-doc{i % 5}-{i}" if paged and rng.rand() < 0.7
                   else f"ctx-{i}")
            kv = make_kv(rng, T=64 + int(rng.randint(4)) * 32)
            keys.append(key)
            ops.append(("insert", key, kv, int(rng.randint(replicas))))
        elif r < 0.75:
            ops.append(("hit", keys[int(rng.randint(len(keys)))]))
        elif r < 0.90 and paged:
            doc = int(rng.randint(5))
            chain = [k for k in keys
                     if k.startswith(f"pg-doc{doc}-")][:4]
            if chain:
                ops.append(("run", f"run-doc{doc}", chain))
        else:
            ops.append(("alpha", float(rng.choice([0.003, 0.01, 0.03]))))
    return ops


def replay(ops, selector, tmp, topology=None):
    """Run one op stream; returns (applied move log, final placements,
    selector stats)."""
    c, clock = build(selector=selector, topology=topology, tmp=tmp)
    c.move_log = []
    for op in ops:
        if op[0] == "tick":
            clock[0] += op[1]
        elif op[0] == "insert":
            c.insert(op[1], op[2], "qa",
                     replica=(op[3] if topology is not None else None))
        elif op[0] == "hit":
            c.fetch(op[1])
        elif op[0] == "run":
            c.note_page_run(len(op[2]), len(op[2]) + 1, run_key=op[1],
                            keys=op[2])
        elif op[0] == "alpha":
            c.policy.alpha = op[1]
    placements = {k: (m.tier, m.method, m.rate, m.nbytes)
                  for k, m in c.meta.items()}
    return c.move_log, placements, dict(c.selector.stats)


def assert_equivalent(ops, tmp_path, topology=None):
    scan_log, scan_place, scan_stats = replay(
        ops, "scan", str(tmp_path / "scan"), topology)
    idx_log, idx_place, idx_stats = replay(
        ops, "indexed", str(tmp_path / "indexed"), topology)
    assert idx_log == scan_log, (
        f"move sequences diverge at index "
        f"{next(i for i, (a, b) in enumerate(zip(idx_log, scan_log)) if a != b)}"
        f" of {len(scan_log)}")
    assert idx_place == scan_place
    # the whole point: identical decisions, far less scoring work
    assert idx_stats["moves_applied"] == scan_stats["moves_applied"]
    if scan_stats["entries_scored"] > 200:
        assert idx_stats["entries_scored"] < scan_stats["entries_scored"]
    return scan_log


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_randomized_equivalence_flat(tmp_path, seed):
    """Whole-context keys, shared-DRAM hierarchy: the indexed selector's
    move log equals the scan's on randomized histories with churn."""
    ops = gen_ops(np.random.RandomState(seed), n_ops=70)
    log = assert_equivalent(ops, tmp_path)
    assert len(log) > 10         # the history actually exercised _enforce


@pytest.mark.parametrize("seed", [10, 11])
def test_randomized_equivalence_runs_and_topology(tmp_path, seed):
    """Page keys + run signals (two half-life classes live at once) on a
    split-DRAM topology: cross-class and cross-tier ordering must still
    match the scan move-for-move."""
    topo = StorageTopology(replicas=2, shared_dram=False)
    ops = gen_ops(np.random.RandomState(seed), n_ops=70, paged=True,
                  replicas=2)
    assert_equivalent(ops, tmp_path, topology=topo)


@pytest.mark.parametrize("spec", [("none", 1.0), ("kivi", 0.28)])
def test_randomized_equivalence_fixed_policy(tmp_path, spec):
    """FixedPolicy ranks by exact recency keys (no decay float path):
    the indexed selector must reproduce its LRU order too."""
    rng = np.random.RandomState(7)
    ops = gen_ops(rng, n_ops=60)
    logs = {}
    for sel in ("scan", "indexed"):
        c, clock = build(selector=sel, policy=spec,
                         tmp=str(tmp_path / f"{sel}_{spec[0]}"))
        c.move_log = []
        for op in ops:
            if op[0] == "tick":
                clock[0] += op[1]
            elif op[0] == "insert":
                c.insert(op[1], op[2], "qa")
            elif op[0] == "hit":
                c.fetch(op[1])
        logs[sel] = (c.move_log,
                     {k: (m.tier, m.rate) for k, m in c.meta.items()})
    assert logs["indexed"] == logs["scan"]


@given(seed=st.integers(0, 10_000), paged=st.booleans(),
       split=st.booleans(), n_ops=st.integers(20, 60))
@settings(max_examples=15, deadline=None)
def test_equivalence_property(tmp_path_factory, seed, paged, split,
                              n_ops):
    """Property form of the equivalence harness: any randomized
    history (topology on/off, runs on/off) yields identical move
    sequences and final placements."""
    topo = (StorageTopology(replicas=2, shared_dram=False)
            if split else None)
    ops = gen_ops(np.random.RandomState(seed), n_ops=n_ops,
                  paged=paged, replicas=2 if split else 1)
    assert_equivalent(ops, tmp_path_factory.mktemp("prop"),
                      topology=topo)


# -- per-tier entry index ----------------------------------------------------

def test_entries_in_tracks_meta_order(tmp_path):
    """``Executor.entries_in`` must list residents in EntryMeta.seq
    order == the meta dict's insertion order (what the scan iterated),
    surviving eviction + re-insert round trips."""
    c, clock = build(tmp=str(tmp_path), dram_mb=2)
    rng = np.random.RandomState(3)
    for i in range(14):
        clock[0] += 1.0
        c.insert(f"e{i}", make_kv(rng), "qa")
    for tname in c.tier_order:
        want = [m.key for m in c.meta.values() if m.tier == tname]
        got = [m.key for m in c.executor.entries_in(tname)]
        assert got == want
        assert {m.key for m in c.executor.iter_entries(tname)} == set(want)
    # seq survives the evict -> reinsert round trip (meta is reused)
    victim = next(k for k, m in c.meta.items() if m.tier is not None)
    seq = c.meta[victim].seq
    from repro.core.policy import Move
    c.executor.apply(Move(victim, "evict", c.meta[victim].tier),
                     c.meta[victim])
    assert victim not in {
        m.key for t in c.tier_order for m in c.executor.iter_entries(t)}
    clock[0] += 1.0
    c.insert(victim, make_kv(rng), "qa")
    assert c.meta[victim].seq == seq


def test_candidate_topk_matches_full_sort(tmp_path):
    """``prefetch_candidates``/``run_candidates`` use nsmallest over the
    index; both must equal the reference filter-then-full-sort."""
    c, clock = build(tmp=str(tmp_path), dram_mb=1, ssd_mb=16)
    rng = np.random.RandomState(9)
    for i in range(18):
        clock[0] += 0.5
        c.insert(f"pg-d{i % 4}-{i}", make_kv(rng, T=96), "qa")
        for _ in range(i % 3):
            clock[0] += 0.1
            c.fetch(f"pg-d{i % 4}-{i}")
        c.note_page_run(1, 1, run_key=f"run-{i % 4}",
                        keys=[f"pg-d{i % 4}-{i}"])
    now = clock[0]
    for min_hz in (0.0, 1e-3):
        for limit in (3, 8, 100):
            slow = [m.key for t in c.tier_order[1:]
                    for m in c.executor.entries_in(t)]
            ref = [k for _, k in sorted(
                ((-c.freq.predict(k, now), k) for k in slow
                 if c.freq.predict(k, now) >= min_hz))][:limit]
            assert c.prefetch_candidates(now, limit=limit,
                                         min_hz=min_hz) == ref
            rref = [(rk, c.page_runs[rk]) for _, rk in sorted(
                ((-c.run_freq.predict(rk, now), rk)
                 for rk in c.page_runs
                 if c.run_freq.predict(rk, now) >= min_hz))][:limit]
            assert c.run_candidates(now, limit=limit, min_hz=min_hz) == rref


# -- cross-check + fault injection -------------------------------------------

def test_crosscheck_agrees_under_pressure(tmp_path):
    """With crosscheck_every=1 every pick re-runs the reference scan:
    a full churny history must complete without a mismatch."""
    c, clock = build(tmp=str(tmp_path))
    c.selector.crosscheck_every = 1
    rng = np.random.RandomState(4)
    for op in gen_ops(rng, n_ops=50):
        if op[0] == "tick":
            clock[0] += op[1]
        elif op[0] == "insert":
            c.insert(op[1], op[2], "qa")
        elif op[0] == "hit":
            c.fetch(op[1])
        elif op[0] == "alpha":
            c.policy.alpha = op[1]
    assert c.selector.stats["crosschecks"] > 0


def test_crosscheck_raises_on_forced_divergence(tmp_path):
    c, clock = build(tmp=str(tmp_path), dram_mb=4)
    rng = np.random.RandomState(5)
    clock[0] = 1.0
    c.insert("a", make_kv(rng), "qa")
    c.insert("b", make_kv(rng), "qa")
    c.selector.crosscheck_every = 1
    c.policy.pick_move_scan = lambda *a, **k: None   # sabotage the ref
    with pytest.raises(SelectorMismatch):
        c.selector.pick_move("dram", clock[0])


def test_make_selector_rejects_unknown(tmp_path):
    c, _ = build(tmp=str(tmp_path))
    assert isinstance(make_selector("scan", c), ScanSelector)
    assert isinstance(make_selector("indexed", c), IndexedSelector)
    with pytest.raises(ValueError):
        make_selector("btree", c)


def test_sanitizer_catches_index_drift(tmp_path):
    """The SimSanitizer index-consistency invariant fires when the
    per-tier index loses a resident, holds a stale meta object, or
    disagrees with the meta's tier."""
    import dataclasses

    c, clock = build(tmp=str(tmp_path), dram_mb=4)
    rng = np.random.RandomState(6)
    clock[0] = 1.0
    c.insert("a", make_kv(rng), "qa")
    tname = c.meta["a"].tier
    san = SimSanitizer(c)
    san.after_event(clock[0], 0)                  # consistent: no raise

    dropped = c.executor.tier_index[tname].pop("a")
    with pytest.raises(SanitizerError, match="index disagrees"):
        SimSanitizer(c).after_event(clock[0], 0)
    c.executor.tier_index[tname]["a"] = dataclasses.replace(dropped)
    with pytest.raises(SanitizerError, match="stale meta"):
        SimSanitizer(c).after_event(clock[0], 0)
    c.executor.tier_index[tname]["a"] = dropped   # restored: consistent
    SimSanitizer(c).after_event(clock[0], 0)


def test_selector_stats_surface_in_controller_stats(tmp_path):
    c, clock = build(tmp=str(tmp_path))
    rng = np.random.RandomState(8)
    for i in range(12):
        clock[0] += 1.0
        c.insert(f"e{i}", make_kv(rng), "qa")
    s = c.stats()
    for k in ("selector_pick_move_calls", "selector_entries_scored",
              "selector_heap_pushes", "selector_moves_applied"):
        assert k in s
    assert s["selector_moves_applied"] > 0
    assert s["selector_heap_pushes"] > 0          # default is indexed
