"""Dense grouped-query-attention transformer with a tied head (Qwen3,
Llama-style): the architecture module of ``"reference": "dense_gqa"``.

The plain float32 forward pass: token embedding, per layer an RMSNorm,
grouped-query attention with per-head query/key RMSNorm where the
configuration has it, rotary position embedding (rotate-half pairing), a
causal softmax, the output projection and a SwiGLU feed-forward, then a
final RMSNorm and logits against the tied embedding. It runs layer by
layer (one compiled layer, called once per layer) and imports nothing of
the program. Its weights, their layout in the program's parameter pytree
and the program's ``ModelConfig`` (``program_config``, the one function
here that imports the program) are given here too; ``bench/reference.py``
says what each name must be.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import Q_CHUNK, mm, padded_tokens, rms, rope

READS = ("hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "vocab_size", "rms_norm_eps", "rope_theta", "qk_norm")
# a published config.json's bookkeeping: naming, token ids, training-only
# settings, and the sliding window's sizes, unused with it off
DESCRIPTIVE = ("architectures", "model_type", "transformers_version",
               "bos_token_id", "eos_token_id", "pad_token_id", "use_cache",
               "initializer_range", "attention_dropout", "sliding_window",
               "max_window_layers")
ASSUMED = {"tie_word_embeddings": True, "hidden_act": "silu",
           "attention_bias": False, "mlp_bias": False, "rope_scaling": None,
           "use_sliding_window": False, "torch_dtype": "bfloat16"}

# the tiny model of a CPU rehearsal (``--rehearse``): every layer of the
# run at a size the CPU tests can hold
REHEARSAL_DIMS = {"n_layers": 2, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                  "vocab": 1024}

LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wi_gate", "wi_up",
                "w_down", "q_norm", "k_norm")
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")


def dims_from_config(cfg: dict, rehearse: bool = False) -> dict:
    """Reference sizes from a configuration file (Hugging Face keys), with
    ``REHEARSAL_DIMS`` over them for a CPU rehearsal."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dims = {"n_layers": cfg["num_hidden_layers"], "d_model": d,
            "n_heads": h, "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim", d // h),
            "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "qk_norm": bool(cfg.get("qk_norm", False)),
            "rope_theta": float(cfg.get("rope_theta", 10000.0)),
            "norm_eps": float(cfg["rms_norm_eps"])}
    return dict(dims, **REHEARSAL_DIMS) if rehearse else dims


def program_config(name: str, dims: dict):
    """The program's ``ModelConfig`` for these sizes (dense GQA, bf16)."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, family="dense", n_layers=dims["n_layers"],
        d_model=dims["d_model"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_ff=dims["d_ff"],
        vocab_size=dims["vocab"], head_dim=dims["head_dim"],
        qk_norm=dims["qk_norm"], rope_theta=dims["rope_theta"],
        norm_eps=dims["norm_eps"], tie_embeddings=True,
        dtype="bfloat16", param_dtype="bfloat16")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def shapes(dims: dict) -> dict:
    """Parameter shapes of one dense GQA model, keyed by leaf name."""
    L, d, f, v = (dims["n_layers"], dims["d_model"], dims["d_ff"],
                  dims["vocab"])
    h, kv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    out = {
        "embed": (v, d), "final_norm": (d,),
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
        "wo": (L, h * hd, d),
        "wi_gate": (L, d, f), "wi_up": (L, d, f), "w_down": (L, f, d),
    }
    if dims["qk_norm"]:
        out["q_norm"] = (L, hd)
        out["k_norm"] = (L, hd)
    return out


def init(key, name: str, shape) -> jax.Array:
    """One leaf in float32. Projections ~ N(0, 1/fan_in), the (tied)
    embedding ~ N(0, 0.02^2); norm weights 1 + N(0, 0.05^2) rather than
    all ones, so that a norm applied to the wrong tensor shows."""
    if name in NORMS:
        return 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def program_layout(flat: dict) -> dict:
    """The same arrays arranged as the program's parameter pytree
    (``params["stack"][0][...]`` with the layer index leading)."""
    attn = {"wq": flat["wq"], "wk": flat["wk"], "wv": flat["wv"],
            "wo": flat["wo"]}
    if "q_norm" in flat:
        attn["q_norm"] = flat["q_norm"]
        attn["k_norm"] = flat["k_norm"]
    layer = {"ln1": flat["ln1"], "attn": attn, "ln2": flat["ln2"],
             "ffn": {"wi_gate": flat["wi_gate"], "wi_up": flat["wi_up"],
                     "wo": flat["w_down"]}}
    return {"embed": flat["embed"], "final_norm": flat["final_norm"],
            "prefix": [], "stack": [layer]}


def param_count(dims: dict) -> int:
    """Parameters a token passes through: the tied embedding counts once,
    as the head."""
    return int(sum(np.prod(s) for s in shapes(dims).values()))


def attn_width(dims: dict) -> int:
    """Query heads x head size, summed over layers: attention FLOPs per
    token pair are 2 (scores) + 2 (values) times this."""
    return dims["n_heads"] * dims["head_dim"] * dims["n_layers"]


def kv_plane(dims: dict) -> tuple:
    """(layers, features) of the K and V arrays the program stores per
    token: the page shape the KIVI kernels compile for."""
    return dims["n_layers"], dims["n_kv_heads"] * dims["head_dim"]


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims_items", "mode"))
def _layer(x, w, dims_items, mode):
    dm = dict(dims_items)
    s = x.shape[0]
    h, kv, hd, eps = (dm["n_heads"], dm["n_kv_heads"], dm["head_dim"],
                      dm["norm_eps"])
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a = rms(x, w["ln1"], eps)
    q = mm(a, w["wq"], mode).reshape(s, h, hd)
    k = mm(a, w["wk"], mode).reshape(s, kv, hd)
    v = mm(a, w["wv"], mode).reshape(s, kv, hd)
    if dm["qk_norm"]:
        q = rms(q, w["q_norm"], eps)
        k = rms(k, w["k_norm"], eps)
    q = rope(q, dm["rope_theta"])
    k = rope(k, dm["rope_theta"])
    g = h // kv
    qg = q.reshape(s, kv, g, hd)
    outs = []
    for c0 in range(0, s, Q_CHUNK):
        qc = qg[c0:c0 + Q_CHUNK]
        n = qc.shape[0]
        sc = mm(qc, k, mode, "qkgd,tkd->kgqt") * hd ** -0.5
        qi = c0 + jnp.arange(n)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(mm(p, v, mode, "kgqt,tkd->qkgd").reshape(n, h * hd))
    o = jnp.concatenate(outs, axis=0)
    x = x + mm(o, w["wo"], mode)
    b = rms(x, w["ln2"], eps)
    ff = jax.nn.silu(mm(b, w["wi_gate"], mode)) * mm(b, w["wi_up"], mode)
    return x + mm(ff, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x_rows, final_norm, embed, eps, mode):
    y = rms(x_rows, final_norm.astype(jnp.float32), eps)
    return mm(y, embed.astype(jnp.float32).T, mode)


def hidden_at(flat: dict, dims: dict, tokens: np.ndarray,
              rows: Sequence[int], mode: str = "fp32", pad_to: int = 0):
    """The last layer's float32 output at positions ``rows`` of the causal
    forward pass over ``tokens``, before the final norm."""
    tok = padded_tokens(tokens, pad_to)
    x = flat["embed"][jnp.asarray(tok)].astype(jnp.float32)
    items = tuple(sorted(dims.items()))
    for li in range(dims["n_layers"]):
        w = {n: flat[n][li] for n in LAYER_LEAVES if n in flat}
        x = _layer(x, w, items, mode)
    return x[jnp.asarray(np.asarray(rows, np.int32))]


def logits_at(flat: dict, dims: dict, tokens: np.ndarray,
              rows: Sequence[int], mode: str = "fp32",
              pad_to: int = 0) -> np.ndarray:
    """float32 logits at positions ``rows``, against the tied embedding."""
    xr = hidden_at(flat, dims, tokens, rows, mode, pad_to)
    return np.asarray(_head(xr, flat["final_norm"], flat["embed"],
                            dims["norm_eps"], mode))
