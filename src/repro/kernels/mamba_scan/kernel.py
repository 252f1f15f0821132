"""Pallas TPU kernel: chunked selective scan (mamba-1).

TPU adaptation (DESIGN.md §4): the GPU mamba kernel is a warp-level
sequential scan; on TPU we tile channels across lanes and parallelize
(batch, channel-tile) on the grid, while the TIME dimension is chunked —
sequential across chunks (state carried in VMEM scratch) and *associative-
scan parallel within a chunk* (log2(Tc) VPU passes instead of Tc):

    h_t = A_t · h0 + B_t,  (A, B) from associative combine
          (a2·a1, a2·b1 + b2) over per-step (exp(dt·a), dt·x·b).

Grid (B, D/dtile, S/Tc); semantics (parallel, parallel, arbitrary).
VMEM per step at Tc=64, dtile=128, N=16: inputs ~0.1 MB + scan temporaries
2·Tc·dtile·N·4B = 8 MB/2... dtile=128,Tc=64,N=16 → 2·64·128·16·4 = 1 MB. OK.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, hT_ref, h_scr, *, tc: int, dtile: int, n: int):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    dt = dt_ref[0].astype(jnp.float32)          # (Tc, dtile)
    x = x_ref[0].astype(jnp.float32)            # (Tc, dtile)
    bs = b_ref[0].astype(jnp.float32)           # (Tc, N)
    cs = c_ref[0].astype(jnp.float32)           # (Tc, N)
    a = a_ref[...].astype(jnp.float32)          # (dtile, N)

    da = jnp.exp(dt[:, :, None] * a[None])                    # (Tc, dtile, N)
    dbx = dt[:, :, None] * x[:, :, None] * bs[:, None, :]     # (Tc, dtile, N)

    def combine(l, r):
        a1, b1 = l
        a2, b2 = r
        return a2 * a1, a2 * b1 + b2

    acum, bcum = jax.lax.associative_scan(combine, (da, dbx), axis=0)
    h0 = h_scr[...]                                           # (dtile, N)
    h_all = acum * h0[None] + bcum                            # (Tc, dtile, N)
    y = jnp.sum(h_all * cs[:, None, :], axis=-1)              # (Tc, dtile)

    y_ref[0] = y.astype(y_ref.dtype)
    h_scr[...] = h_all[-1]

    @pl.when(t_idx == pl.num_programs(2) - 1)
    def _finalize():
        hT_ref[0] = h_scr[...].astype(hT_ref.dtype)


def selective_scan(dt, x, bs, cs, a, h0, *, tc: int = 64, dtile: int = 128,
                   interpret: bool = True):
    """Shapes as in ref.py. Returns (y (B,S,D) f32, hT (B,D,N) f32)."""
    bsz, s, d = x.shape
    n = bs.shape[-1]
    tc = min(tc, s)
    dtile = min(dtile, d)
    assert s % tc == 0 and d % dtile == 0, (s, tc, d, dtile)
    grid = (bsz, d // dtile, s // tc)
    kern = functools.partial(_scan_kernel, tc=tc, dtile=dtile, n=n)
    y, hT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tc, dtile), lambda b, dd, t: (b, t, dd)),   # dt
            pl.BlockSpec((1, tc, dtile), lambda b, dd, t: (b, t, dd)),   # x
            pl.BlockSpec((1, tc, n), lambda b, dd, t: (b, t, 0)),        # B
            pl.BlockSpec((1, tc, n), lambda b, dd, t: (b, t, 0)),        # C
            pl.BlockSpec((dtile, n), lambda b, dd, t: (dd, 0)),          # A
            pl.BlockSpec((1, dtile, n), lambda b, dd, t: (b, dd, 0)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, tc, dtile), lambda b, dd, t: (b, t, dd)),   # y
            pl.BlockSpec((1, dtile, n), lambda b, dd, t: (b, dd, 0)),    # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dtile, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, x, bs, cs, a, h0)
    return y, hT
