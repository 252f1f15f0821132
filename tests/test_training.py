"""Training substrate: learning, int8 state, accumulation, checkpoints,
fault tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.training.checkpoint import CheckpointManager
from repro.training.data import Pipeline, PipelineConfig
from repro.training.optimizer import (
    AdamWConfig, adamw_init, adamw_update, cosine_schedule, wsd_schedule,
)
from repro.training.train_step import (
    init_train_state, make_train_step,
)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-135m", smoke=True)
    model = build_model(cfg)
    return cfg, model


def test_loss_decreases(setup):
    cfg, model = setup
    opt = AdamWConfig(lr=wsd_schedule(3e-3, 5, 30, 20))
    state = init_train_state(model, jax.random.key(0), opt)
    step = jax.jit(make_train_step(model, opt))
    # "lm" motif stream: learnable to low loss quickly (the "recall" task
    # needs an induction circuit — real but slow; covered by test_system)
    pipe = Pipeline(PipelineConfig(cfg.vocab_size, 96, 8, kind="lm"))
    losses = []
    for _ in range(40):
        b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0]


def test_int8_state_learns_like_f32(setup):
    """8-bit optimizer state must preserve optimization QUALITY (loss
    trajectory), not bitwise parameter equality — quantized-m noise where
    v~0 makes per-step updates differ by design (clipped)."""
    cfg, model = setup
    losses = {}
    for int8 in (False, True):
        opt = AdamWConfig(lr=2e-3, int8_state=int8)
        from repro.training.train_step import TrainState
        state = TrainState(model.init(jax.random.key(0)),
                           adamw_init(opt, model.init(jax.random.key(0))))
        step = jax.jit(make_train_step(model, opt))
        pipe = Pipeline(PipelineConfig(cfg.vocab_size, 96, 8, kind="lm"))
        traj = []
        for _ in range(25):
            b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
            state, m = step(state, b)
            traj.append(float(m["loss"]))
        losses[int8] = traj
    # both must learn; int8 final loss within 50% of f32 final loss
    assert losses[False][-1] < 0.7 * losses[False][0]
    assert losses[True][-1] < 0.7 * losses[True][0]
    assert losses[True][-1] < max(1.5 * losses[False][-1],
                                  losses[False][-1] + 0.5)


def test_grad_accumulation_equivalence(setup):
    cfg, model = setup
    opt = AdamWConfig(lr=1e-3)
    pipe = Pipeline(PipelineConfig(cfg.vocab_size, 64, 8, kind="lm"))
    batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
    s1 = init_train_state(model, jax.random.key(0), opt)
    s2 = init_train_state(model, jax.random.key(0), opt)
    step1 = jax.jit(make_train_step(model, opt, accum_steps=1, remat=False))
    step2 = jax.jit(make_train_step(model, opt, accum_steps=2, remat=False))
    s1, m1 = step1(s1, batch)
    b2 = {k: v.reshape(2, 4, *v.shape[1:]) for k, v in batch.items()}
    s2, m2 = step2(s2, b2)
    # same data split in two microbatches -> numerically close update
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def test_schedules():
    wsd = wsd_schedule(1.0, 10, 50, 40)
    assert float(wsd(jnp.int32(0))) == 0.0
    assert float(wsd(jnp.int32(10))) == pytest.approx(1.0)
    assert float(wsd(jnp.int32(40))) == pytest.approx(1.0)   # stable
    assert float(wsd(jnp.int32(100))) == pytest.approx(0.1)  # decayed
    cos = cosine_schedule(1.0, 10, 100)
    assert float(cos(jnp.int32(10))) == pytest.approx(1.0)
    assert float(cos(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)


def test_checkpoint_roundtrip_and_gc(tmp_path, setup):
    cfg, model = setup
    opt = AdamWConfig(lr=1e-3)
    state = init_train_state(model, jax.random.key(0), opt)
    cm = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in (10, 20, 30):
        cm.save(s, state, extra={"step": s})
    assert cm.latest_step() == 30
    restored, extra = cm.restore()
    assert extra["step"] == 30
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    import os
    kept = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert sorted(kept) == ["step_20", "step_30"]    # keep=2 GC


def test_checkpoint_crc_detection(tmp_path, setup):
    cfg, model = setup
    opt = AdamWConfig(lr=1e-3)
    state = init_train_state(model, jax.random.key(0), opt)
    cm = CheckpointManager(str(tmp_path), async_write=False)
    cm.save(1, state)
    import glob, json
    man = glob.glob(str(tmp_path / "step_1" / "manifest.json"))[0]
    j = json.load(open(man))
    first = next(iter(j["leaves"]))
    j["leaves"][first]["crc32"] ^= 1
    json.dump(j, open(man, "w"))
    with pytest.raises(IOError):
        cm.restore()


def test_data_pipeline_determinism_and_sharding():
    cfgp = PipelineConfig(512, 64, 4, kind="recall", seed=7)
    a = Pipeline(cfgp, host_id=0, n_hosts=2)
    b = Pipeline(cfgp, host_id=0, n_hosts=2)
    np.testing.assert_array_equal(a.next_batch()["tokens"],
                                  b.next_batch()["tokens"])
    c = Pipeline(cfgp, host_id=1, n_hosts=2)
    assert not np.array_equal(a.next_batch()["tokens"],
                              c.next_batch()["tokens"])
    # cursor restore
    st = a.state()
    x1 = a.next_batch()["tokens"]
    a2 = Pipeline(cfgp, host_id=0, n_hosts=2)
    a2.restore(st)
    np.testing.assert_array_equal(a2.next_batch()["tokens"], x1)


def test_fault_tolerance_primitives():
    from repro.runtime.fault_tolerance import (
        HeartbeatMonitor, StragglerDetector, elastic_plan,
    )
    t = [0.0]
    deaths = []
    hb = HeartbeatMonitor(deadline_s=10, on_death=deaths.append,
                          clock=lambda: t[0])
    hb.register("w0")
    hb.register("w1")
    t[0] = 5
    hb.beat("w0")
    t[0] = 12
    assert hb.sweep() == ["w1"] and deaths == ["w1"]
    assert hb.alive_workers() == ["w0"]
    hb.beat("w1")                      # rejoin
    assert "w1" in hb.alive_workers()

    sd = StragglerDetector(threshold=2.0, min_samples=4)
    for i in range(8):
        sd.record("fast", 1.0)
        sd.record("slow", 3.5)
    assert sd.stragglers() == ["slow"]

    assert elastic_plan(512, 16, pods=2) == (2, 16, 16)
    assert elastic_plan(192, 16) == (12, 16)
    with pytest.raises(ValueError):
        elastic_plan(8, 16)


def test_compressed_psum_error_feedback():
    """int8 gradient compression: quantization error is captured in the
    EF residual so (reduced + residual) reconstructs the exact sum."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.training.train_step import compressed_psum

    mesh = make_mesh((1,), ("d",))
    x = jnp.asarray(np.random.RandomState(0).randn(64).astype(np.float32))

    def f(x):
        red, err = compressed_psum(x, "d")
        return red, err

    red, err = jax.jit(shard_map(f, mesh=mesh, in_specs=P(),
                                 out_specs=(P(), P())))(x)
    # one shard: reduced + residual == original exactly
    np.testing.assert_allclose(np.asarray(red) + np.asarray(err),
                               np.asarray(x), rtol=1e-6, atol=1e-6)
    # and the wire payload was int8-coarse: reduced != x in general
    assert float(jnp.abs(red - x).max()) > 0
