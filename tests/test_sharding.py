"""Sharding rules + distributed execution correctness (subprocess with
forced host devices where >1 device is needed)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.launch import specs as sp
from repro.launch.mesh import make_mesh
from repro.launch.sharding import constrain, use_mesh
from repro.models import build_model

# subprocess tests compile multi-host-device train steps — minutes each
pytestmark = pytest.mark.slow


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, ("data", None))
    assert y is x


def test_guard_drops_nondivisible_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    s = sp.sharding(mesh, (7, 16), "data", "model")
    assert s.spec == jax.sharding.PartitionSpec(None, None) or \
        mesh.shape["data"] == 1      # trivially fine on 1x1


@pytest.mark.parametrize("name", ASSIGNED)
def test_param_shardings_cover_all_leaves(name):
    cfg = get_config(name, smoke=True)
    model = build_model(cfg)
    shapes = model.init_shapes()
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = sp.param_shardings(shapes, mesh)
    n_leaves = len(jax.tree.leaves(shapes))
    n_sh = len(jax.tree.leaves(sh, is_leaf=lambda x: isinstance(
        x, jax.sharding.NamedSharding)))
    assert n_leaves == n_sh


_DISTRIBUTED_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import build_model
    from repro.launch import specs as sp
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import use_mesh
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_step import init_train_state, make_train_step

    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    opt = AdamWConfig(lr=1e-3)
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (8, 16), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(2), (8, 16), 0,
                                     cfg.vocab_size),
    }
    # single-device reference
    state0 = init_train_state(model, jax.random.key(0), opt)
    step = make_train_step(model, opt, remat=False)
    _, m0 = jax.jit(step)(state0, batch)

    # 4x2 mesh distributed
    mesh = make_mesh((4, 2), ("data", "model"))
    state_sh = sp.train_state_shardings(
        jax.eval_shape(lambda: init_train_state(model, jax.random.key(0),
                                                opt)), mesh)
    state = init_train_state(model, jax.random.key(0), opt)
    state = jax.tree.map(jax.device_put, state, state_sh)
    bsh = {k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
           for k, v in batch.items()}
    def stepm(s, b):
        with use_mesh(mesh):
            return step(s, b)
    with mesh:
        _, m1 = jax.jit(stepm, in_shardings=(state_sh, None))(state, bsh)
    print(json.dumps({"loss0": float(m0["loss"]), "loss1": float(m1["loss"])}))
""")


def test_distributed_matches_single_device():
    """4x2-mesh sharded train step == single-device step (same loss)."""
    r = subprocess.run([sys.executable, "-c", _DISTRIBUTED_SNIPPET],
                       capture_output=True, text=True, timeout=560,
                       env={**os.environ, "XLA_FLAGS": "",
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert abs(out["loss0"] - out["loss1"]) < 2e-3, out


_EP_MOE_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import moe as M
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import use_mesh

    cfg = get_config("olmoe-1b-7b", smoke=True)
    p = M.init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model)) * 0.5
    out_plain, _ = M.moe_fwd(p, cfg, x, dropless=True)
    mesh = make_mesh((2, 4), ("data", "model"))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    def f(p, x):
        with use_mesh(mesh):
            return M.moe_fwd_ep(p, cfg, x, dropless=True)
    with mesh:
        out_ep, _ = jax.jit(f)(p, xs)
    rel = float(jnp.abs(out_ep - out_plain).max()
                / (jnp.abs(out_plain).max() + 1e-9))
    print(json.dumps({"rel": rel}))
""")


def test_ep_moe_matches_plain():
    """shard_map expert-parallel MoE == single-device reference."""
    r = subprocess.run([sys.executable, "-c", _EP_MOE_SNIPPET],
                       capture_output=True, text=True, timeout=560,
                       env={**os.environ, "XLA_FLAGS": "",
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["rel"] < 1e-4, out


def test_cache_shardings_decode_vs_long():
    import os
    cfg = get_config("jamba-1.5-large-398b", smoke=True)
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    cache_shapes = jax.eval_shape(lambda: model.init_cache(2, 64))
    sh_dec = sp.cache_shardings(cache_shapes, mesh, long_context=False)
    sh_long = sp.cache_shardings(cache_shapes, mesh, long_context=True)
    # structure mirrors the cache pytree
    assert (jax.tree.structure(sh_dec, is_leaf=lambda x: isinstance(
        x, jax.sharding.NamedSharding)) ==
        jax.tree.structure(cache_shapes))
    assert (jax.tree.structure(sh_long, is_leaf=lambda x: isinstance(
        x, jax.sharding.NamedSharding)) ==
        jax.tree.structure(cache_shapes))
