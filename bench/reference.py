"""Plain float32 reference of a dense GQA transformer, and its fp8 control.

This is the architecture's forward pass written out in ``jax.numpy``:
token embedding, per layer an RMSNorm, grouped-query attention with
per-head query/key RMSNorm where the configuration has it, rotary
position embedding (rotate-half pairing), a causal softmax, the output
projection and a SwiGLU feed-forward, then a final RMSNorm and logits
against the tied embedding. It imports nothing of the program and has no
cache, kernel or batching. Every matrix product runs at
``precision="highest"`` in float32 so that the TPU does not round it to
bfloat16.

It runs layer by layer (one compiled layer, called once per layer), with
the sequence padded to a multiple of ``BUCKET`` (a cell pads every
request to its longest, so that one shape compiles); causal masking makes
the padding invisible to real positions.
Logits are formed only at the positions asked for.

``mode="fp8"`` is the control: the same computation with both operands of
every matrix product rounded to float8 e4m3 first, the step below the
bfloat16 the configurations state. A served path that computed so would
have to fail the comparison.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 512
Q_CHUNK = 512


def dims_from_config(cfg: dict) -> dict:
    """Reference sizes from a configuration file (Hugging Face keys)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"n_layers": cfg["num_hidden_layers"], "d_model": d,
            "n_heads": h, "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim", d // h),
            "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "qk_norm": bool(cfg.get("qk_norm", False)),
            "rope_theta": float(cfg.get("rope_theta", 10000.0)),
            "norm_eps": float(cfg["rms_norm_eps"])}


def _round(x, mode: str):
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, mode: str, spec: str = None):
    a, b = _round(a, mode), _round(b, mode)
    if spec is None:
        return jnp.matmul(a, b, precision="highest")
    return jnp.einsum(spec, a, b, precision="highest")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, D) with positions 0..S-1; rotate-half pairing."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims_items", "mode"))
def _layer(x, w, dims_items, mode):
    dm = dict(dims_items)
    s = x.shape[0]
    h, kv, hd, eps = (dm["n_heads"], dm["n_kv_heads"], dm["head_dim"],
                      dm["norm_eps"])
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a = _rms(x, w["ln1"], eps)
    q = _mm(a, w["wq"], mode).reshape(s, h, hd)
    k = _mm(a, w["wk"], mode).reshape(s, kv, hd)
    v = _mm(a, w["wv"], mode).reshape(s, kv, hd)
    if dm["qk_norm"]:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q = _rope(q, dm["rope_theta"])
    k = _rope(k, dm["rope_theta"])
    g = h // kv
    qg = q.reshape(s, kv, g, hd)
    outs = []
    for c0 in range(0, s, Q_CHUNK):
        qc = qg[c0:c0 + Q_CHUNK]
        n = qc.shape[0]
        sc = _mm(qc, k, mode, "qkgd,tkd->kgqt") * hd ** -0.5
        qi = c0 + jnp.arange(n)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm(p, v, mode, "kgqt,tkd->qkgd").reshape(n, h * hd))
    o = jnp.concatenate(outs, axis=0)
    x = x + _mm(o, w["wo"], mode)
    b = _rms(x, w["ln2"], eps)
    ff = jax.nn.silu(_mm(b, w["wi_gate"], mode)) * _mm(b, w["wi_up"], mode)
    return x + _mm(ff, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x_rows, final_norm, embed, eps, mode):
    y = _rms(x_rows, final_norm.astype(jnp.float32), eps)
    return _mm(y, embed.astype(jnp.float32).T, mode)


LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wi_gate", "wi_up",
                "w_down", "q_norm", "k_norm")


def logits_at(flat: dict, dims: dict, tokens: np.ndarray,
              rows: Sequence[int], mode: str = "fp32",
              pad_to: int = 0) -> np.ndarray:
    """float32 logits at positions ``rows`` of the causal forward pass over
    ``tokens``; ``flat`` is the weights by leaf name (``weights.make_flat``).
    The sequence is padded to ``pad_to`` (at least its own length rounded
    up to ``BUCKET``), so that one shape serves a whole cell."""
    t = len(tokens)
    padded = -(-max(t, pad_to) // BUCKET) * BUCKET
    tok = np.zeros(padded, np.int32)
    tok[:t] = tokens
    x = flat["embed"][jnp.asarray(tok)].astype(jnp.float32)
    items = tuple(sorted(dims.items()))
    for li in range(dims["n_layers"]):
        w = {n: flat[n][li] for n in LAYER_LEAVES if n in flat}
        x = _layer(x, w, items, mode)
    xr = x[jnp.asarray(np.asarray(rows, np.int32))]
    return np.asarray(_head(xr, flat["final_norm"], flat["embed"],
                            dims["norm_eps"], mode))


def served_gaps(flat: dict, dims: dict, prompt: np.ndarray,
                served: Sequence[int], mode: str = "fp32",
                pad_to: int = 0) -> np.ndarray:
    """Per served token, how far its reference logit lies below the
    reference's best logit at that position (0 where it is the best).

    ``prompt`` is the context and question, ``served`` the answer tokens
    the program produced after it; the reference is teacher-forced on
    both."""
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served[:-1], np.int64)])
    rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(served)))
    lg = logits_at(flat, dims, seq, rows, mode, pad_to)
    return lg.max(axis=-1) - lg[np.arange(len(served)), np.asarray(served)]


def control_gaps(flat: dict, dims: dict, prompt: np.ndarray,
                 served: Sequence[int], pad_to: int = 0) -> np.ndarray:
    """The fp8 control at the same prompts and tokens: per position, how
    far the reference's logit of the token fp8 puts first lies below the
    reference's best."""
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served[:-1], np.int64)])
    rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(served)))
    ref = logits_at(flat, dims, seq, rows, "fp32", pad_to)
    low = logits_at(flat, dims, seq, rows, "fp8", pad_to)
    top = low.argmax(axis=-1)
    return ref.max(axis=-1) - ref[np.arange(len(served)), top]


def widest(gaps: List[np.ndarray]) -> float:
    return float(max(float(np.max(g)) for g in gaps)) if gaps else float("nan")
