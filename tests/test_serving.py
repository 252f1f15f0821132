"""Serving engine integration: runner conversions, engine e2e, metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving.metrics import codebleu_proxy, rouge_l, token_f1
from repro.serving.runner import ModelRunner, cache_to_kvdata, kvdata_to_cache
from repro.serving.timemodel import A100, TimeModel
from repro.serving.workload import make_contexts, poisson_requests


@pytest.fixture(scope="module")
def runner():
    cfg = get_config("adaptcache-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return ModelRunner(model, params, capacity=256)


def test_metrics_bounds_and_identity():
    for fn in (token_f1, rouge_l, codebleu_proxy):
        assert fn([1, 2, 3], [1, 2, 3]) == pytest.approx(1.0)
        assert 0.0 <= fn([1, 2, 3], [4, 5, 6]) <= 1.0
        assert fn([], []) == 1.0
        assert fn([1], []) == 0.0


def test_kvdata_cache_roundtrip(runner):
    """decode from converted cache == decode from the original cache."""
    cfg = runner.model.cfg
    toks = np.asarray(jax.random.randint(jax.random.key(1), (20,), 0,
                                         cfg.vocab_size))
    kv = runner.prefill_entry(toks)
    assert kv["k"].shape[0] == cfg.n_layers
    assert kv["k"].shape[1] == 20
    ans1 = runner.generate_from_kvdata(kv, 20, np.array([5, 6]), 8)
    ans2 = runner.generate_from_kvdata(kv, 20, np.array([5, 6]), 8)
    assert ans1 == ans2                        # deterministic
    # full uncompressed generation equals teacher path
    ans3, kv2 = runner.generate_uncompressed(toks, np.array([5, 6]), 8)
    assert ans3 == ans1
    np.testing.assert_allclose(kv2["k"], kv["k"], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "deepseek-v2-lite-16b",
                                  "jamba-1.5-large-398b"])
def test_kvdata_roundtrip_other_families(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    r = ModelRunner(model, params, capacity=64)
    toks = np.asarray(jax.random.randint(jax.random.key(2), (16,), 0,
                                         cfg.vocab_size))
    kv = r.prefill_entry(toks)
    out = r.generate_from_kvdata(kv, 16, np.array([3]), 4)
    assert len(out) == 4


def test_time_model_scaling():
    cfg = get_config("adaptcache-8b")
    tm = TimeModel(cfg, A100, n_active_params=8_030_000_000)
    assert tm.prefill_s(2000) == pytest.approx(2 * tm.prefill_s(1000))
    # decode becomes KV-read bound for long contexts
    short = tm.decode_step_s(8, 512)
    long = tm.decode_step_s(8, 65536)
    assert long > short


def test_engine_end_to_end(tmp_path):
    from repro.serving.baselines import build_engine
    from repro.serving.engine import summarize
    rng = np.random.RandomState(0)
    cfg = get_config("adaptcache-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    runner = ModelRunner(model, params, capacity=512)
    contexts = make_contexts(rng, cfg.vocab_size, 2, min_len=96, max_len=192,
                             n_probes=1)
    reqs = poisson_requests(rng, contexts, rate_hz=0.5, duration_s=24)
    full = get_config("adaptcache-8b")
    rig = build_engine(runner, contexts, full, 8_030_000_000,
                       policy="adaptive", alpha=0.01, dram_entries=1.5,
                       ssd_entries=4.0, ssd_root=str(tmp_path / "a"))
    res = rig.engine.process(reqs, skip_quality=True)
    s = summarize(res)
    assert s["n"] == len(reqs)
    assert 0 < s["hit_rate"] <= 1.0
    # repeated contexts must eventually hit
    assert s["hit_rate"] > 0.2

    # prefill baseline: all misses, TTFT dominated by prefill
    rig_p = build_engine(runner, contexts, full, 8_030_000_000,
                         policy="prefill", ssd_root=str(tmp_path / "b"))
    res_p = rig_p.engine.process(reqs, skip_quality=True)
    s_p = summarize(res_p)
    assert s_p["hit_rate"] == 0.0
    assert s_p["ttft_mean_s"] > s["ttft_mean_s"]


def test_workload_statistics():
    rng = np.random.RandomState(3)
    ctxs = make_contexts(rng, 512, 3, n_probes=2)
    assert len(ctxs) == 9
    assert {c.task_type for c in ctxs} == {"qa", "summarization", "coding"}
    reqs = poisson_requests(rng, ctxs, rate_hz=2.0, duration_s=100)
    assert 120 < len(reqs) < 300                 # ~200 expected
    arr = np.array([r.arrival_s for r in reqs])
    assert (np.diff(arr) >= 0).all()


def test_serve_untrained_and_full_width(tmp_path):
    """``--train-steps 0`` serves the untrained smoke model (it used to
    crash on an unbound loss); ``--full-width`` serves the named arch's
    own widths with seeded weights."""
    from repro.launch import serve
    common = ["--policy", "kivi:0.16", "--rate", "2", "--duration", "2",
              "--contexts-per-task", "1"]
    for argv, want in ((["--arch", "qwen3-1.7b", "--train-steps", "0"],
                        "qwen3-1.7b-smoke"),
                       (["--arch", "qwen3-1.7b-smoke", "--full-width"],
                        "qwen3-1.7b-smoke")):
        args = serve.parse_args(argv + common)
        runner = serve.load_runner(args)
        assert runner.model.cfg.name == want
        _, requests, results, s = serve.serve(args, runner)
        assert len(results) == len(requests) > 0
        assert s["n"] == len(requests)


def test_enable_compile_cache(monkeypatch):
    """The env var wins and is left alone; otherwise the cache sits at a
    fixed path inside the checkout."""
    from repro.launch import serve
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert serve.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = serve.enable_compile_cache()
        assert path == str(serve.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert serve.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_lane_write_casts_to_cache_dtype():
    """Stored entries are float32; a bf16 model's lane cache stays bf16
    and admission warns about no implicit dtype change."""
    import dataclasses
    import warnings
    from repro.serving.scheduler import ContinuousBatcher
    from repro.serving.workload import Request
    cfg = dataclasses.replace(get_config("qwen3-1.7b", smoke=True),
                              dtype="bfloat16", param_dtype="bfloat16")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    runner = ModelRunner(model, params, capacity=64)
    kv = runner.prefill_entry(np.arange(12, dtype=np.int32))
    assert kv["k"].dtype == np.float32
    tm = TimeModel(get_config("qwen3-1.7b"), A100, 2_000_000_000)
    b = ContinuousBatcher(model, params, tm, n_slots=2, capacity=64)
    assert b.device == jax.devices()[0]
    req = Request(0, "c", np.array([3], np.int32), 0.0, "qa", 2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        b.admit(1, req, kv, 12, 0.0)
    k = b.cache["stack"][0]["self"]["k"]
    assert k.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(k[:, 1, :12], np.float32).reshape(cfg.n_layers, 12, -1),
        kv["k"].astype(jnp.bfloat16).astype(np.float32))


_REPLICA_DEVICES_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.serving import engine as E
from repro.serving.baselines import build_engine
from repro.serving.runner import ModelRunner
from repro.serving.workload import make_contexts, poisson_requests

seen = []
class Recording(E.ContinuousBatcher):
    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        seen.append(self)
E.ContinuousBatcher = Recording
cfg = get_config("qwen3-1.7b", smoke=True)
model = build_model(cfg)
runner = ModelRunner(model, model.init(jax.random.key(0)), capacity=256)
rng = np.random.RandomState(0)
ctxs = make_contexts(rng, cfg.vocab_size, 2, min_len=64, max_len=128,
                     n_probes=1)
reqs = poisson_requests(rng, ctxs, rate_hz=4e6, duration_s=3e-6)
rig = build_engine(runner, ctxs, get_config("qwen3-1.7b"), 2_000_000_000,
                   policy=("none", 1.0), n_replicas=4)
res = rig.engine.process(reqs, skip_quality=True)
print(json.dumps({
    "devices": [str(b.device) for b in seen],
    "on_device": [all(x.devices() == {b.device}
                      for x in jax.tree.leaves((b.params, b.cache)))
                  for b in seen],
    "served": [sum(r.replica == i for r in res) for i in range(4)],
    "n": len(res), "n_req": len(reqs)}))
"""


def test_replicas_run_on_their_own_devices():
    """Replica r's params and lane cache live on device r mod n, and the
    router spreads a burst over all four."""
    import json
    import os
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "-c", _REPLICA_DEVICES_SNIPPET],
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "XLA_FLAGS": "",
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == [f"TFRT_CPU_{i}" for i in range(4)]
    assert all(out["on_device"])
    assert out["n"] == out["n_req"] and all(c > 0 for c in out["served"])
