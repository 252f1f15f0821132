"""Launch machinery: dry-run cell end-to-end in a subprocess (forced host
devices), roofline math, elastic checkpoint restore across mesh sizes."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_shape
from repro.launch import roofline as rl


def test_model_flops_includes_attention():
    cfg = get_config("qwen3-1.7b")
    tr = get_shape("train_4k")
    mf = rl.model_flops(cfg, tr, 2_030_000_000, 2_030_000_000)
    dense = 6.0 * 2_030_000_000 * tr.global_batch * tr.seq_len
    assert mf > dense                     # attention term present
    dec = get_shape("decode_32k")
    mfd = rl.model_flops(cfg, dec, 2_030_000_000, 2_030_000_000)
    assert mfd < mf / 100                 # decode is tiny compute


def test_analytic_memory_quantized_kv():
    cfg = get_config("qwen3-1.7b")
    dec = get_shape("decode_32k")
    full = rl.analytic_hbm_bytes(cfg, dec, 2_030_000_000, 2_030_000_000,
                                 256, kv_bits=16)
    q4 = rl.analytic_hbm_bytes(cfg, dec, 2_030_000_000, 2_030_000_000,
                               256, kv_bits=4)
    assert q4 < 0.45 * full               # KV dominates; ~4x on that part


def test_roofline_bottleneck_logic():
    r = rl.Roofline("a", "s", "m", 256, flops=197e12, hbm_bytes=1.0,
                    collective_bytes=1.0, collective_detail={},
                    model_flops_per_chip=100e12)
    assert r.bottleneck == "compute"
    assert r.t_compute == pytest.approx(1.0)
    assert 0 < r.roofline_fraction <= 1.0


_DRYRUN_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax
    from repro.launch import dryrun as dr
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh
    # shrink the mesh for CI speed: monkeypatch the factory
    import repro.launch.mesh as mesh_mod
    mesh_mod.make_production_mesh = lambda multi_pod=False: (
        mesh_mod.make_mesh((4, 4), ("data", "model")))
    dr.make_production_mesh = mesh_mod.make_production_mesh
    res = dr.run_cell("smollm-135m", "decode_32k", multi_pod=False,
                      verbose=False)
    print(json.dumps({"status": res["status"],
                      "bottleneck": res.get("bottleneck"),
                      "fits": res.get("fits_hbm")}))
""")


def test_dryrun_cell_subprocess():
    """A full dry-run cell (lower+compile+roofline) on a 4x4 mesh."""
    r = subprocess.run([sys.executable, "-c", _DRYRUN_SNIPPET],
                       capture_output=True, text=True, timeout=560,
                       env={**os.environ, "XLA_FLAGS": "",
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["status"] == "ok", out


_ELASTIC_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    from repro.launch import specs as sp
    from repro.launch.mesh import make_mesh
    from repro.training.checkpoint import CheckpointManager
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_step import init_train_state

    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    opt = AdamWConfig(lr=1e-3)
    mesh_a = make_mesh((4, 2), ("data", "model"))
    sh_a = sp.train_state_shardings(
        jax.eval_shape(lambda: init_train_state(model, jax.random.key(0),
                                                opt)), mesh_a)
    state = jax.tree.map(jax.device_put,
                         init_train_state(model, jax.random.key(0), opt),
                         sh_a)
    d = tempfile.mkdtemp()
    cm = CheckpointManager(d, async_write=False)
    cm.save(1, state, extra={"step": 1})
    # elastic restore: 8 devices -> 4 (downscale), new mesh (2, 2)
    mesh_b = make_mesh((2, 2), ("data", "model"))
    sh_b = sp.train_state_shardings(
        jax.eval_shape(lambda: init_train_state(model, jax.random.key(0),
                                                opt)), mesh_b)
    restored, extra = cm.restore(shardings=sh_b)
    ok = all(np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(jax.tree.leaves(state),
                             jax.tree.leaves(restored)))
    some_leaf = jax.tree.leaves(restored)[3]
    print(json.dumps({"equal": bool(ok), "step": extra["step"],
                      "ndev": len(some_leaf.sharding.device_set)}))
""")


def test_elastic_checkpoint_restore_subprocess():
    """Checkpoint written on a (4,2) mesh restores bit-exactly onto (2,2)."""
    r = subprocess.run([sys.executable, "-c", _ELASTIC_SNIPPET],
                       capture_output=True, text=True, timeout=560,
                       env={**os.environ, "XLA_FLAGS": "",
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["equal"] and out["step"] == 1, out
    assert out["ndev"] == 4
