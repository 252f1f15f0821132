"""AdaptCache policy + storage tiers: utility math, MCKP moves, capacity."""
import numpy as np
import pytest

from repro.core.compression import default_registry
from repro.core.controller import AdaptCacheController
from repro.core.estimator import (
    DEFAULT_DECOMPRESS_BPS, DelayProfile, FrequencyEstimator, QualityEstimator,
)
from repro.core.policy import AdaptivePolicy, FixedPolicy
from repro.storage.tier import DRAMTier, DeviceSpec, SSDTier

RNG = np.random.RandomState(5)


def make_kv(T=128, L=2, F=64):
    return {"k": RNG.randn(L, T, F).astype(np.float32),
            "v": RNG.randn(L, T, F).astype(np.float32),
            "positions": np.arange(T, dtype=np.int32)}


def build(policy="adaptive", alpha=0.01, dram_mb=2, ssd_mb=16, tmp=None):
    methods = default_registry()
    tiers = {"dram": DRAMTier(DeviceSpec("dram", dram_mb << 20, 16e9, 16e9,
                                         20e-6)),
             "ssd": SSDTier(DeviceSpec("ssd", ssd_mb << 20, 1e9, 1e9, 1e-4),
                            root=tmp)}
    order = ["dram", "ssd"]
    q = QualityEstimator()
    q.set_curve("qa", "kivi", [(0.09, 0.8), (0.16, 0.92), (0.28, 0.98)])
    q.set_curve("qa", "streaming_llm",
                [(0.125, 0.5), (0.25, 0.7), (0.5, 0.88), (1.0, 1.0)])
    q.set_curve("qa", "drop_kivi", [(0.02, 0.4), (0.05, 0.6), (0.14, 0.85)])
    f = FrequencyEstimator(halflife_s=600)
    dp = DelayProfile(dict(DEFAULT_DECOMPRESS_BPS))
    pol = (AdaptivePolicy(methods, tiers, order, q, f, dp, alpha=alpha)
           if policy == "adaptive" else FixedPolicy(methods, order, *policy))
    clock = [0.0]
    return AdaptCacheController(methods, tiers, order, pol, dp, f,
                                clock=lambda: clock[0]), clock


def test_capacity_never_exceeded(tmp_path):
    c, clock = build(tmp=str(tmp_path))
    for i in range(40):
        clock[0] += 1
        c.insert(f"e{i}", make_kv(T=128 + (i % 3) * 64), "qa")
        for t in ("dram", "ssd"):
            assert c.tiers[t].used_bytes <= c.tiers[t].spec.capacity_bytes


def test_fetch_roundtrip_and_stats(tmp_path):
    c, clock = build(tmp=str(tmp_path))
    kv = make_kv()
    c.insert("x", kv, "qa")
    r = c.fetch("x")
    assert r is not None and r.tier in ("dram", "ssd")
    assert r.kv["k"].shape[0] == kv["k"].shape[0]
    assert r.total_delay_s > 0
    assert c.fetch("missing") is None
    s = c.stats()
    assert s["hits"] == 1 and s["misses"] == 1


def test_alpha_controls_compression_aggressiveness(tmp_path):
    """Paper §3: smaller alpha -> more aggressive compression -> more
    entries resident in DRAM."""
    counts = {}
    for alpha in (1.0, 0.001):
        c, clock = build(alpha=alpha, tmp=str(tmp_path / str(alpha)))
        for i in range(30):
            clock[0] += 1
            c.insert(f"e{i}", make_kv(), "qa")
            clock[0] += 0.1
            c.fetch(f"e{i}")
        counts[alpha] = sum(1 for m in c.meta.values() if m.tier == "dram")
    assert counts[0.001] > counts[1.0]


def test_lru_policy_evicts_oldest(tmp_path):
    c, clock = build(policy=("none", 1.0), dram_mb=1, ssd_mb=1,
                     tmp=str(tmp_path))
    for i in range(24):
        clock[0] += 1
        c.insert(f"e{i}", make_kv(), "qa")
    # oldest entries must be gone (evicted through ssd), newest present
    assert c.lookup("e23") is not None
    assert c.lookup("e0") is None


def test_reinsert_after_eviction_preserves_history(tmp_path):
    """Regression: re-inserting a key whose meta survived eviction
    (tier is None) must keep its hits/last_hit history and EWMA state —
    the utility ranking runs on them — instead of silently rebuilding a
    fresh EntryMeta."""
    c, clock = build(policy=("none", 1.0), dram_mb=1, ssd_mb=1,
                     tmp=str(tmp_path))
    c.insert("x", make_kv(), "qa")
    clock[0] += 1
    c.fetch("x")
    clock[0] += 1
    c.fetch("x")
    assert c.meta["x"].hits == 2
    from repro.core.policy import Move
    c.executor.apply(Move("x", "evict", c.meta["x"].tier), c.meta["x"])
    assert c.lookup("x") is None and "x" in c.meta
    last_hit = c.meta["x"].last_hit
    clock[0] += 1
    c.insert("x", make_kv(), "qa")
    m = c.meta["x"]
    assert m.tier is not None
    assert m.hits == 2                      # history survived the round trip
    assert m.last_hit == last_hit
    assert c.freq._rate["x"] > c.freq.prior_hz   # EWMA not reset to prior


def test_ssd_crc_detection(tmp_path):
    from repro.core.compression.base import CompressedEntry
    tier = SSDTier(DeviceSpec("ssd", 1 << 30, 1e9, 1e9), root=str(tmp_path))
    entry = CompressedEntry("none", 1.0, {"k": np.ones((4, 4), np.float32)},
                            {})
    tier.put("a", entry)
    path = tier.entry_info("a")["path"]
    blob = bytearray(open(path, "rb").read())
    blob[-3] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    with pytest.raises(Exception):
        tier.get("a")


def test_dram_tier_accounting():
    from repro.core.compression.base import CompressedEntry
    tier = DRAMTier(DeviceSpec("dram", 1 << 20, 1e9, 1e9))
    e = CompressedEntry("none", 1.0, {"k": np.zeros((100,), np.float32)}, {})
    tier.put("a", e)
    assert tier.used_bytes == 400
    tier.put("a", e)                  # replace, not double-count
    assert tier.used_bytes == 400
    tier.evict("a")
    assert tier.used_bytes == 0 and not tier.has("a")


def test_pick_move_frees_bytes_or_none(tmp_path):
    """Policy invariant: every move returned by pick_move frees bytes in
    the tier it names; when no freeing move exists it returns None."""
    c, clock = build(tmp=str(tmp_path), dram_mb=1, ssd_mb=4)
    pol = c.policy
    for i in range(20):
        clock[0] += 1
        c.insert(f"e{i}", make_kv(T=96 + (i % 4) * 32), "qa")
        for tname in ("dram", "ssd"):
            entries = c._entries_in(tname)
            move = pol.pick_move(tname, entries, clock[0],
                                 kv_lookup=c.executor.proxies.get)
            if entries:
                assert move is None or (move.freed_bytes > 0
                                        and move.tier == tname)
            else:
                assert move is None


def test_enforce_terminates_within_capacity(tmp_path):
    """_enforce must terminate with every tier within capacity even when a
    single entry exceeds the fast tier (cascade demote -> evict)."""
    c, clock = build(tmp=str(tmp_path), dram_mb=1, ssd_mb=1)
    for i in range(10):
        clock[0] += 1
        c.insert(f"big{i}", make_kv(T=640), "qa")    # ~>0.3 MB each
        for t in ("dram", "ssd"):
            assert c.tiers[t].used_bytes <= c.tiers[t].spec.capacity_bytes


def test_ssd_roundtrip_preserves_bytes(tmp_path):
    from repro.core.compression.base import CompressedEntry
    from repro.storage.tier import SSDTier, DeviceSpec
    arrays = {"k": RNG.randn(3, 17, 5).astype(np.float32),
              "v": RNG.randn(3, 17, 5).astype(np.float32),
              "positions": np.arange(17, dtype=np.int32)}
    tier = SSDTier(DeviceSpec("ssd", 1 << 30, 1e9, 1e9), root=str(tmp_path))
    entry = CompressedEntry("none", 1.0, arrays, {})
    tier.put("a", entry)
    back = tier.get("a")
    assert back.method == "none" and back.rate == 1.0
    for name, arr in arrays.items():
        np.testing.assert_array_equal(back.arrays[name], arr)
        assert back.arrays[name].dtype == arr.dtype


def test_ssd_evict_tolerates_unlinked_file(tmp_path):
    import os
    from repro.core.compression.base import CompressedEntry
    tier = SSDTier(DeviceSpec("ssd", 1 << 30, 1e9, 1e9), root=str(tmp_path))
    entry = CompressedEntry("none", 1.0,
                            {"k": np.ones((4, 4), np.float32)}, {})
    tier.put("gone", entry)
    os.unlink(tier.entry_info("gone")["path"])      # out-of-band deletion
    tier.evict("gone")                              # must not raise
    assert not tier.has("gone") and tier.used_bytes == 0


def test_compose_basic_properties():
    """Unit pins for QualityEstimator.compose: empty -> 1.0, uniform
    keeps the score, geometric mean punishes a weak link harder than
    the arithmetic mean, token weights bias toward the longer piece."""
    compose = QualityEstimator.compose
    assert compose([]) == 1.0
    assert compose([0.7, 0.7, 0.7]) == pytest.approx(0.7)
    mixed = compose([1.0, 0.25])
    assert mixed == pytest.approx(0.5)            # < arithmetic 0.625
    assert compose([1.0, 0.0, 1.0]) == 0.0
    # remainder weighting: 64-token perfect page + 8-token lossy tail
    # scores far above the unweighted mean
    assert compose([1.0, 0.5], [64, 8]) > compose([1.0, 0.5])


def test_run_aware_depth_discounted_utility(tmp_path):
    """PR-6 tentpole: pg-*/rem-* entries rank by their RUN's EWMA
    discounted by page depth — a deep page of a hot run still out-ranks
    any page of a cold run, and depth orders pages within one run."""
    from repro.core.estimator import RunFrequencyEstimator

    c, clock = build(tmp=str(tmp_path), dram_mb=8)
    pol = c.policy
    assert pol.run_freq is c.run_freq       # controller auto-binds
    run_freq = RunFrequencyEstimator(halflife_s=600)
    pol.bind_run_signals(run_freq, {"pg-hot-0": "pg-hot-0",
                                    "pg-hot-1": "pg-hot-0",
                                    "rem-hot-2": "pg-hot-0",
                                    "pg-cold-0": "pg-cold-0"}.get)
    t = 1.0
    for _ in range(30):                     # hot run hit repeatedly
        run_freq.note_run("pg-hot-0", t)
        t += 0.2
    run_freq.note_run("pg-cold-0", t)       # cold run seen once
    hot0 = pol._entry_freq("pg-hot-0", t)
    hot1 = pol._entry_freq("pg-hot-1", t)
    rem2 = pol._entry_freq("rem-hot-2", t)
    cold = pol._entry_freq("pg-cold-0", t)
    # depth discount orders one run's pages: page0 > page1 > remainder
    assert hot0 > hot1 > rem2
    assert hot1 == pytest.approx(hot0 * pol.depth_discount)
    assert rem2 == pytest.approx(hot0 * pol.depth_discount ** 2)
    # the hot run's DEEPEST entry still beats the cold run's first page
    assert rem2 > cold
    # unknown runs and whole-context keys fall back to the per-entry EWMA
    assert (pol._entry_freq("pg-unknown-0", t)
            == pol.freq.predict("pg-unknown-0", t))
    assert pol._entry_freq("qa-3", t) == pol.freq.predict("qa-3", t)


def test_evict_is_ladder_rung_on_every_tier(tmp_path):
    """EVICPRESS: eviction is scored on the same drop-per-byte scale as
    recompress/demote on EVERY tier. With alpha=0 a resident entry's
    utility is strictly negative (pure delay), so evicting it from the
    FAST tier is a strict improvement the greedy must take directly —
    not a demotion that shuffles the negative utility to the SSD."""
    from repro.core.entry import EntryMeta

    c, clock = build(alpha=0.0, tmp=str(tmp_path), dram_mb=8)
    pol = c.policy
    clock[0] = 1.0
    c.insert("e0", make_kv(T=128), "qa")
    meta = c.meta["e0"]
    assert meta.tier is not None
    assert pol.current_utility(meta, clock[0]) < 0
    mv = pol.pick_move(meta.tier, [meta], clock[0],
                       kv_lookup=c.executor.proxies.get)
    assert mv.kind == "evict" and mv.tier == meta.tier
    assert mv.drop_per_byte < 0            # removing it is an improvement
    # with a positive quality weight the same entry is NOT evicted from
    # DRAM: recompression/demotion preserve utility more cheaply
    c2, clock2 = build(alpha=10.0, tmp=str(tmp_path / "pos"), dram_mb=8)
    clock2[0] = 1.0
    c2.insert("e0", make_kv(T=128), "qa")
    m2 = c2.meta["e0"]
    for _ in range(5):
        clock2[0] += 0.2
        c2.fetch("e0")
    mv2 = c2.policy.pick_move(m2.tier, [m2], clock2[0],
                              kv_lookup=c2.executor.proxies.get)
    assert mv2 is not None and mv2.kind != "evict"


def test_marginal_utility_prefers_cheap_drop(tmp_path):
    """The greedy must pick recompression of a low-value entry over
    evicting a high-frequency one."""
    c, clock = build(alpha=0.01, dram_mb=1, ssd_mb=64, tmp=str(tmp_path))
    clock[0] = 1
    c.insert("hot", make_kv(T=192), "qa")
    for _ in range(20):
        clock[0] += 0.2
        c.fetch("hot")
    for i in range(12):
        clock[0] += 1
        c.insert(f"cold{i}", make_kv(T=192), "qa")
    assert c.lookup("hot") is not None     # hot entry survived somewhere
