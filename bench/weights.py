"""Seeded weights for a dense GQA transformer, made on the device.

The benchmark, not the program, makes the weights: one jitted call turns
``--seed`` into every parameter in bfloat16, in the pytree layout the
program's model reads (``params["stack"][0][...]`` with the layer index
leading). The plain reference makes them again from the same seed after
the program's state is freed, so it takes nothing the program made.

Scales follow the usual initialisation: projections ~ N(0, 1/fan_in),
the (tied) embedding ~ N(0, 0.02^2). Norm weights are 1 + N(0, 0.05^2)
rather than all ones, so that a norm applied to the wrong tensor shows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (64 bits are fine)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def shapes(dims: dict) -> dict:
    """Parameter shapes of one dense GQA model, keyed by leaf path."""
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


def _draw(key, name: str, shape) -> jax.Array:
    k = jax.random.fold_in(key, sum(map(ord, name)) * 7919 + len(name))
    if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
        w = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
    elif name == "embed":
        w = 0.02 * jax.random.normal(k, shape, jnp.float32)
    else:
        fan_in = shape[-2]
        w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
    return w.astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _maker(dims_items: tuple):
    dims = dict(dims_items)
    shp = shapes(dims)
    return jax.jit(lambda key: {n: _draw(key, n, s) for n, s in shp.items()})


def make_flat(dims: dict, seed: int) -> dict:
    """Every parameter, keyed by leaf name, from one jitted call."""
    return _maker(tuple(sorted(dims.items())))(seed_key(seed))


def program_layout(flat: dict) -> dict:
    """The same arrays arranged as the program's parameter pytree."""
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
    return int(sum(np.prod(s) for s in shapes(dims).values()))
