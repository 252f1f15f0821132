"""MiniCPM (arXiv:2404.06395; ``modeling_minicpm.py``): the architecture
module of ``"reference": "minicpm"``.

A Llama-style block, multi-head attention (as many key/value heads as
query heads), rotary position embedding (rotate-half pairing), SwiGLU,
RMSNorm and a head tied to the embedding, with three muP scalings read
from the configuration:

* ``scale_emb``: the token embeddings are multiplied by it;
* ``scale_depth``: each residual branch, attention and feed-forward, is
  multiplied by ``scale_depth / sqrt(num_hidden_layers)`` before its add;
* ``dim_model_base``: the final normed hidden state is divided by
  ``hidden_size / dim_model_base`` before the tied head.

The plain float32 forward pass below runs layer by layer (one compiled
layer, called once per layer) and imports nothing of the program. The
weights' shapes, their layout in the program's parameter pytree and the
counts come from ``dense_gqa``, whose tree this model shares; the
program's ``ModelConfig`` (``program_config``, the one function here that
imports the program) is ``dense_gqa``'s with the scalings set.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.reference import Q_CHUNK, mm, padded_tokens, rms, rope

dense = reference.load("dense_gqa")

SCALINGS = ("scale_emb", "scale_depth", "dim_model_base")
# rope_theta is not in MiniCPM's config.json; the library's default, 10000,
# is read where a file leaves it out
READS = ("hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "vocab_size",
         "rms_norm_eps", "rope_theta") + SCALINGS
DESCRIPTIVE = ("architectures", "model_type", "transformers_version",
               "bos_token_id", "eos_token_id", "pad_token_id", "use_cache",
               "initializer_range")
ASSUMED = {"tie_word_embeddings": True, "hidden_act": "silu",
           "attention_bias": False, "mlp_bias": False, "rope_scaling": None,
           "torch_dtype": "bfloat16"}

# the tiny model of a CPU rehearsal: 4 layers, so that the depth scaling
# (1.4 / sqrt(4)) is far from 1, and d_model / dim_model_base = 8, so that
# the logits spread about as dense_gqa's rehearsal's do
REHEARSAL_DIMS = {"n_layers": 4, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 4, "head_dim": 32, "d_ff": 256,
                  "vocab": 1024, "dim_model_base": 16}

# std of the tied embedding: at the published widths the head's logits
# then spread as qwen3-1.7b's do, 0.17 * sqrt(2304) / 9 = 0.91 against
# 0.02 * sqrt(2048) = 0.91, the spread the mixes' logit-gap limits were
# set for. The published initializer_range, 0.1, would give 0.53.
EMBED_STD = 0.17
# gain of the residual branches' output projections (``wo``, ``w_down``)
# over N(0, 1/fan_in). With a tied head and scale_emb, a token's own
# embedding, x12 at the input, reaches the head and scores its own row:
# by 576 * EMBED_STD / R standard deviations of the other logits at
# d_model 2304, where R is the residual stream's RMS at the last layer,
# about 1.4 x the branches' output RMS whatever the depth (40 branches of
# 1.4 / sqrt(40)). At gain 1 every position predicts its own input token
# with a margin of ~37, and no rounding can move an argmax. At 400 the
# margin is 0.17 standard deviations, near qwen3-1.7b's 0.15.
BRANCH_GAIN = 400.0

shapes = dense.shapes
program_layout = dense.program_layout
param_count = dense.param_count
attn_width = dense.attn_width
kv_plane = dense.kv_plane


def dims_from_config(cfg: dict, rehearse: bool = False) -> dict:
    """Reference sizes and scalings from a configuration file (Hugging
    Face keys), with ``REHEARSAL_DIMS`` over them for a CPU rehearsal. A
    file without one of the three scalings is refused: without it this is
    another model."""
    for key in SCALINGS:
        if key not in cfg:
            raise ValueError(f"{key!r} is missing: architecture 'minicpm' "
                             f"reads all of {SCALINGS}")
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    if kv != h:
        raise ValueError(f"num_key_value_heads {kv} != num_attention_heads "
                         f"{h}: architecture 'minicpm' is multi-head")
    dims = {"n_layers": cfg["num_hidden_layers"], "d_model": d,
            "n_heads": h, "n_kv_heads": kv, "head_dim": d // h,
            "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "qk_norm": False,
            "rope_theta": float(cfg.get("rope_theta", 10000.0)),
            "norm_eps": float(cfg["rms_norm_eps"]),
            "scale_emb": float(cfg["scale_emb"]),
            "scale_depth": float(cfg["scale_depth"]),
            "dim_model_base": int(cfg["dim_model_base"])}
    return dict(dims, **REHEARSAL_DIMS) if rehearse else dims


def program_config(name: str, dims: dict):
    """The program's ``ModelConfig``: dense_gqa's with the scalings."""
    return dataclasses.replace(dense.program_config(name, dims),
                               **{k: dims[k] for k in SCALINGS})


def init(key, name: str, shape) -> jax.Array:
    """One leaf in float32: ``dense_gqa``'s, but the tied embedding
    ~ N(0, EMBED_STD^2) and the branches' output projections
    ~ N(0, BRANCH_GAIN^2 / fan_in)."""
    if name == "embed":
        return EMBED_STD * jax.random.normal(key, shape, jnp.float32)
    if name in ("wo", "w_down"):
        return BRANCH_GAIN * dense.init(key, name, shape)
    return dense.init(key, name, shape)


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims_items", "mode"))
def _layer(x, w, dims_items, mode):
    dm = dict(dims_items)
    s = x.shape[0]
    h, hd, eps = dm["n_heads"], dm["head_dim"], dm["norm_eps"]
    branch = dm["scale_depth"] / math.sqrt(dm["n_layers"])
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a = rms(x, w["ln1"], eps)
    q = rope(mm(a, w["wq"], mode).reshape(s, h, hd), dm["rope_theta"])
    k = rope(mm(a, w["wk"], mode).reshape(s, h, hd), dm["rope_theta"])
    v = mm(a, w["wv"], mode).reshape(s, h, hd)
    outs = []
    for c0 in range(0, s, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        n = qc.shape[0]
        sc = mm(qc, k, mode, "qhd,thd->hqt") * hd ** -0.5
        qi = c0 + jnp.arange(n)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(mm(p, v, mode, "hqt,thd->qhd").reshape(n, h * hd))
    o = jnp.concatenate(outs, axis=0)
    x = x + mm(o, w["wo"], mode) * branch
    b = rms(x, w["ln2"], eps)
    ff = jax.nn.silu(mm(b, w["wi_gate"], mode)) * mm(b, w["wi_up"], mode)
    return x + mm(ff, w["w_down"], mode) * branch


@functools.partial(jax.jit, static_argnames=("eps", "div", "mode"))
def _head(x_rows, final_norm, embed, eps, div, mode):
    y = rms(x_rows, final_norm.astype(jnp.float32), eps) / div
    return mm(y, embed.astype(jnp.float32).T, mode)


def logits_at(flat: dict, dims: dict, tokens: np.ndarray,
              rows: Sequence[int], mode: str = "fp32",
              pad_to: int = 0) -> np.ndarray:
    """float32 logits at positions ``rows`` of the causal forward pass
    over ``tokens``, against the tied embedding."""
    tok = padded_tokens(tokens, pad_to)
    x = flat["embed"][jnp.asarray(tok)].astype(jnp.float32) \
        * dims["scale_emb"]
    items = tuple(sorted(dims.items()))
    for li in range(dims["n_layers"]):
        w = {n: flat[n][li] for n in dense.LAYER_LEAVES if n in flat}
        x = _layer(x, w, items, mode)
    xr = x[jnp.asarray(np.asarray(rows, np.int32))]
    return np.asarray(_head(xr, flat["final_norm"], flat["embed"],
                            dims["norm_eps"],
                            dims["d_model"] / dims["dim_model_base"], mode))
