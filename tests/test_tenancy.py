"""Multi-tenant SLO layer: per-tenant ledgers, quota-aware eviction,
the Sarathi-style budgeted compute tick, and the per-tenant summary
schema (pinned storm regression + hypothesis properties)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.compression import default_registry
from repro.core.controller import AdaptCacheController
from repro.core.estimator import (
    DEFAULT_DECOMPRESS_BPS, DelayProfile, FrequencyEstimator,
    QualityEstimator,
)
from repro.core.policy import AdaptivePolicy, FixedPolicy
from repro.models import build_model
from repro.serving.baselines import build_engine
from repro.serving.engine import summarize
from repro.serving.metrics import percentile_summary
from repro.serving.runner import ModelRunner
from repro.serving.workload import (
    Request, Tenant, make_prefix_sharing_contexts, make_tenant_workload,
)
from repro.storage.tier import DRAMTier, DeviceSpec, SSDTier

FULL = "adaptcache-8b"
N_ACTIVE = 8_030_000_000
RNG = np.random.RandomState(12)


# -- percentile_summary schema ----------------------------------------------

def test_percentile_summary_empty_sample_keeps_schema():
    """An empty sample must emit the FULL key set at 0.0 — CSV writers
    key columns off the first row, so a dropped p99 would silently
    shift every later row's fields."""
    full = percentile_summary("itl", [0.1, 0.2, 0.3])
    empty = percentile_summary("itl", [])
    want = {"itl_mean_s", "itl_p50_s", "itl_p90_s", "itl_p99_s"}
    assert set(full) == set(empty) == want
    assert all(v == 0.0 for v in empty.values())
    assert full["itl_p99_s"] >= full["itl_p50_s"] >= 0.1


# -- controller-level: ledgers + quota eviction ------------------------------

def make_kv(T=64, L=2, F=64):
    return {"k": RNG.randn(L, T, F).astype(np.float32),
            "v": RNG.randn(L, T, F).astype(np.float32),
            "positions": np.arange(T, dtype=np.int32)}


def build_ctrl(policy="none", alpha=0.01, dram_mb=64, ssd_mb=256,
               tmp=None):
    methods = default_registry()
    tiers = {"dram": DRAMTier(DeviceSpec("dram", dram_mb << 20, 16e9,
                                         16e9, 20e-6)),
             "ssd": SSDTier(DeviceSpec("ssd", ssd_mb << 20, 1e9, 1e9,
                                       1e-4), root=tmp)}
    order = ["dram", "ssd"]
    q = QualityEstimator()
    q.set_curve("qa", "kivi", [(0.09, 0.8), (0.16, 0.92), (0.28, 0.98)])
    f = FrequencyEstimator(halflife_s=600)
    dp = DelayProfile(dict(DEFAULT_DECOMPRESS_BPS))
    pol = (AdaptivePolicy(methods, tiers, order, q, f, dp, alpha=alpha)
           if policy == "adaptive"
           else FixedPolicy(methods, order, "none", 1.0))
    clock = [0.0]
    return AdaptCacheController(methods, tiers, order, pol, dp, f,
                                clock=lambda: clock[0]), clock


def _assert_ledger_consistent(ctrl):
    """The executor ledger must agree with a fresh recount over
    ``controller.meta`` per (tier, tenant), and each tier's buckets must
    sum to its used_bytes — the same invariant SimSanitizer enforces."""
    want = {name: {} for name in ctrl.tiers}
    for m in ctrl.meta.values():
        if m.tier and m.nbytes:
            b = want[m.tier]
            ten = m.tenant or ""
            b[ten] = b.get(ten, 0) + m.nbytes
    for name, tier in ctrl.tiers.items():
        have = ctrl.executor.tenant_ledger.get(name, {})
        assert have == want[name], \
            f"tier {name}: ledger {have} != recount {want[name]}"
        assert sum(have.values()) == tier.used_bytes


@pytest.mark.parametrize("policy", ["none", "adaptive"])
def test_ledger_tracks_every_byte_mutation(policy, tmp_path):
    """Insert / re-insert / fetch-promote / capacity-evict all keep the
    per-tenant ledger exact, for both the lossless and the
    compress-happy policy (recompress + demote paths)."""
    ctrl, clock = build_ctrl(policy, dram_mb=1, ssd_mb=8,
                             tmp=str(tmp_path))
    for i in range(24):
        clock[0] += 1.0
        ten = ("alice", "bob", None)[i % 3]
        ctrl.insert(f"e{i}", make_kv(T=64 + 32 * (i % 3)), "qa",
                    tenant=ten)
        _assert_ledger_consistent(ctrl)
        if i % 4 == 0:
            clock[0] += 0.1
            ctrl.fetch(f"e{i}")          # hit accounting / promotion
            _assert_ledger_consistent(ctrl)
    # both tenants plus the untenanted bucket saw traffic
    resident = {t: ctrl.tenant_resident_bytes(t) for t in ("alice", "bob")}
    assert all(v >= 0 for v in resident.values())
    ledger = ctrl.executor.tenant_ledger
    seen = {ten for b in ledger.values() for ten in b}
    assert seen & {"alice", "bob"}


@pytest.mark.parametrize("policy", ["none", "adaptive"])
def test_quota_eviction_holds_quota_and_spares_other_tenants(policy,
                                                             tmp_path):
    """With capacity slack (quota is the ONLY pressure), a storming
    tenant is clamped to its quota after every insert while the other
    tenant's residency is untouched."""
    ctrl, clock = build_ctrl(policy, tmp=str(tmp_path))
    kv_bytes = sum(a.nbytes for a in make_kv().values())
    quota = int(2.5 * kv_bytes)
    ctrl.set_tenant_quotas({"storm": quota})
    for i in range(3):
        clock[0] += 1.0
        ctrl.insert(f"calm{i}", make_kv(), "qa", tenant="calm")
    calm_before = ctrl.tenant_resident_bytes("calm")
    assert calm_before > 0
    for i in range(10):
        clock[0] += 1.0
        ctrl.insert(f"storm{i}", make_kv(), "qa", tenant="storm")
        assert ctrl.tenant_resident_bytes("storm") <= quota
        _assert_ledger_consistent(ctrl)
    assert ctrl.counters["quota_evictions"] > 0
    # quota eviction only ever sheds the owing tenant's bytes
    assert ctrl.tenant_resident_bytes("calm") == calm_before
    # quota'd entries that survived are the RECENT ones (LRU victims)
    survivors = {k for k, m in ctrl.meta.items()
                 if m.tenant == "storm" and m.tier}
    assert "storm9" in survivors and "storm0" not in survivors


def test_unquotad_tenant_is_never_quota_evicted(tmp_path):
    ctrl, clock = build_ctrl(tmp=str(tmp_path))
    ctrl.set_tenant_quotas({"other": 1})
    for i in range(6):
        clock[0] += 1.0
        ctrl.insert(f"f{i}", make_kv(), "qa", tenant="free")
    assert ctrl.counters["quota_evictions"] == 0
    assert sum(1 for m in ctrl.meta.values()
               if m.tenant == "free" and m.tier) == 6


def test_quota_and_ledger_hypothesis_properties(tmp_path):
    """For ANY interleaving of tenanted inserts and fetches: (a) each
    tier's ledger buckets recount exactly and sum to used_bytes, and
    (b) no quota'd tenant ever exceeds its quota after an insert."""
    from hypothesis import given, settings, strategies as st

    quota = 3 * sum(a.nbytes for a in make_kv(T=64).values())
    quotas = {"a": quota, "b": 2 * quota}

    @settings(deadline=None, max_examples=15)
    @given(st.lists(st.tuples(st.sampled_from(["a", "b", "c", None]),
                              st.sampled_from([32, 64, 96]),
                              st.booleans()),
                    min_size=1, max_size=24))
    def prop(ops):
        ctrl, clock = build_ctrl(dram_mb=2, ssd_mb=8,
                                 tmp=str(tmp_path / f"h{len(ops)}"))
        ctrl.set_tenant_quotas(quotas)
        for i, (ten, T, refetch) in enumerate(ops):
            clock[0] += 1.0
            ctrl.insert(f"k{i}", make_kv(T=T), "qa", tenant=ten)
            if refetch:
                clock[0] += 0.1
                ctrl.fetch(f"k{i}")
            _assert_ledger_consistent(ctrl)
            for name, q in quotas.items():
                assert ctrl.tenant_resident_bytes(name) <= q

    prop()


# -- engine-level: budgeted compute tick -------------------------------------

@pytest.fixture(scope="module")
def runner():
    cfg = get_config(FULL, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return ModelRunner(model, params, capacity=256)


STORM_TENANTS = {
    "hi": Tenant("hi", tier=0, ttft_slo_s=0.05, tasks=("qa",)),
    "lo": Tenant("lo", tier=2, tasks=("coding",)),
}
CHUNK = 16


def _storm_workload(vocab):
    """Steady short-context hi-tenant traffic + a burst of cold
    long-context lo-tenant prefills landing mid-run (distinct contexts,
    so no coalescing: every storm request is a multi-chunk job)."""
    rng = np.random.RandomState(31)
    hi_ctx = make_prefix_sharing_contexts(rng, vocab, n_docs=2,
                                          n_variants=1, prefix_len=32,
                                          suffix_len=16, n_probes=2,
                                          tasks=("qa",))
    lo_ctx = make_prefix_sharing_contexts(rng, vocab, n_docs=4,
                                          n_variants=1, prefix_len=96,
                                          suffix_len=32, n_probes=1,
                                          tasks=("coding",))
    for c in hi_ctx:
        c.key, c.tenant = f"hi:{c.key}", "hi"
    for c in lo_ctx:
        c.key, c.tenant = f"lo:{c.key}", "lo"
    reqs = []
    for i in range(8):
        ctx = hi_ctx[i % len(hi_ctx)]
        reqs.append(Request(0, ctx.key, ctx.probes[i % len(ctx.probes)],
                            0.01 + i * 0.04, ctx.task_type,
                            max_new_tokens=6, tenant="hi"))
    for i, ctx in enumerate(lo_ctx):
        reqs.append(Request(0, ctx.key, ctx.probes[0], 0.15 + i * 0.002,
                            ctx.task_type, max_new_tokens=1, tenant="lo"))
    reqs.sort(key=lambda r: (r.arrival_s, r.context_key))
    for i, r in enumerate(reqs):
        r.req_id = i
    return hi_ctx + lo_ctx, reqs


def _run_storm(runner, token_budget, tmp):
    full = get_config(FULL)
    contexts, requests = _storm_workload(runner.model.cfg.vocab_size)
    rig = build_engine(runner, contexts, full, N_ACTIVE,
                       policy=("none", 1.0), dram_entries=6.0,
                       ssd_entries=24.0, n_lanes=6, ssd_root=tmp,
                       chunk_tokens=CHUNK, token_budget=token_budget,
                       tenants=STORM_TENANTS.values())
    res = rig.engine.process(requests, skip_quality=True)
    s = summarize(res, chunk_stats=rig.engine.chunk_stats)
    max_past = max(len(c.tokens) for c in contexts)
    return s, rig.engine.tm.chunk_prefill_s(CHUNK, max_past)


def test_prefill_storm_budgeted_tick_bounds_decode(runner, tmp_path):
    """Pinned regression for the tentpole contract: FIFO interleave
    books every queued storm chunk ahead of the next decode tick
    (max tick delay blows past the single-chunk ceiling); the budgeted
    tick admits one budget per tick, so the hi tenant's decode delay
    and p99 inter-token latency stay bounded."""
    fifo, ceiling_s = _run_storm(runner, 0, str(tmp_path / "fifo"))
    budgeted, _ = _run_storm(runner, CHUNK, str(tmp_path / "budget"))
    # the budget must engage (chunks deferred into the priority queue)
    # and must not leak into the FIFO baseline
    assert budgeted["chunk_chunks_deferred"] > 0
    assert budgeted["chunk_defer_wait_s"] > 0.0
    assert fifo["chunk_chunks_deferred"] == 0
    assert fifo["chunk_defer_wait_s"] == 0.0
    # both modes prefill the same chunk volume
    assert (budgeted["chunk_chunks_issued"]
            >= fifo["chunk_chunks_issued"] > 0)
    # FIFO violates the single-chunk decode-delay bound; budgeted holds
    assert fifo["chunk_tick_delay_max_s"] > ceiling_s
    assert budgeted["chunk_tick_delay_max_s"] <= ceiling_s + 1e-9
    # and that bound is what keeps the hi tenant's ITL down
    assert (budgeted["tenant_hi_itl_p99_s"]
            < fifo["tenant_hi_itl_p99_s"])


def test_budget_requires_unified_tick(runner):
    full = get_config(FULL)
    contexts, _ = _storm_workload(runner.model.cfg.vocab_size)
    with pytest.raises(ValueError, match="chunk_tokens"):
        build_engine(runner, contexts, full, N_ACTIVE,
                     policy=("none", 1.0), token_budget=32)


def test_summarize_per_tenant_keys_gated(runner, tmp_path):
    """Per-tenant percentile keys appear exactly when results carry a
    tenant; untenanted runs keep the historical schema."""
    s, _ = _run_storm(runner, CHUNK, str(tmp_path / "keys"))
    for ten in ("hi", "lo"):
        assert s[f"tenant_{ten}_n"] > 0
        for stat in ("ttft", "itl"):
            for pct in ("mean", "p50", "p90", "p99"):
                assert f"tenant_{ten}_{stat}_{pct}_s" in s
    from repro.serving.workload import make_contexts, round_robin_requests
    rng = np.random.RandomState(3)
    ctxs = make_contexts(rng, runner.model.cfg.vocab_size, 2, min_len=64,
                         max_len=96, n_probes=2)
    full = get_config(FULL)
    rig = build_engine(runner, ctxs, full, N_ACTIVE, policy=("none", 1.0),
                       dram_entries=1.5, ssd_entries=8.0)
    res = rig.engine.process(round_robin_requests(ctxs, 6, 0.02,
                                                  max_new_tokens=2),
                             skip_quality=True)
    s0 = summarize(res)
    assert not any(k.startswith("tenant_") for k in s0)


def test_sanitized_tenant_run_clean_and_bit_identical(runner, tmp_path):
    """A quota'd multi-tenant diurnal run under the SimSanitizer (which
    now audits the tenant ledger every event) finds nothing, and the
    sanitized replay is bit-identical to the unsanitized one."""
    full = get_config(FULL)
    rng_a, rng_b = (np.random.RandomState(47) for _ in range(2))
    tenants = [Tenant("chat", tier=0, quota_tokens=256, ttft_slo_s=0.05,
                      rate_scale=1.0, tasks=("qa",)),
               Tenant("agent", tier=2, quota_tokens=128, rate_scale=0.6,
                      phase=0.5, tasks=("coding",))]
    outs, rigs = [], []
    for sanitize, rng in ((False, rng_a), (True, rng_b)):
        contexts, requests = make_tenant_workload(
            rng, runner.model.cfg.vocab_size, n_docs_per_tenant=3,
            tenants=tenants, base_rate_hz=25.0, duration_s=2.0)
        rig = build_engine(runner, contexts, full, N_ACTIVE,
                           policy="adaptive", dram_entries=2.0,
                           ssd_entries=8.0,
                           ssd_root=str(tmp_path / f"s{sanitize}"),
                           tenants=tenants, sanitize=sanitize)
        res = rig.engine.process(requests, skip_quality=True)
        outs.append([(r.req_id, r.ttft_s, r.hit_tier, r.tenant)
                     for r in res])
        rigs.append(rig)
    assert outs[0] == outs[1]
    san = rigs[1].engine.last_sanitizer
    assert san is not None and san.events_checked > 0
    assert san.violations == 0
    # the quotas were binding and held
    tok_bytes = runner.model.cfg.kv_bytes_per_token() * 2.0
    assert rigs[1].controller.counters["quota_evictions"] > 0
    for t in tenants:
        assert (rigs[1].controller.tenant_resident_bytes(t.name)
                <= int(t.quota_tokens * tok_bytes))
