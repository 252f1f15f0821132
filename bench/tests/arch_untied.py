"""A second architecture for the tests: ``dense_gqa`` with an untied
output head (``lm_head``, d_model x vocab), which the program builds with
``ModelConfig.tie_embeddings=False``. It is entered as new files only,
this module and a configuration naming it, and reuses the dense GQA
layers."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.reference import mm, rms

dense = reference.load("dense_gqa")

READS = dense.READS
DESCRIPTIVE = dense.DESCRIPTIVE
ASSUMED = dict(dense.ASSUMED, tie_word_embeddings=False)
dims_from_config = dense.dims_from_config
attn_width = dense.attn_width
kv_plane = dense.kv_plane
init = dense.init


def program_config(name: str, dims: dict):
    return dataclasses.replace(dense.program_config(name, dims),
                               tie_embeddings=False)


def shapes(dims: dict) -> dict:
    return dict(dense.shapes(dims), lm_head=(dims["d_model"], dims["vocab"]))


def program_layout(flat: dict) -> dict:
    return dict(dense.program_layout(flat), lm_head=flat["lm_head"])


def param_count(dims: dict) -> int:
    """The embedding is a lookup: only the head's copy of its size counts."""
    d, v = dims["d_model"], dims["vocab"]
    return int(sum(np.prod(s) for s in shapes(dims).values())) - d * v


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x_rows, final_norm, lm_head, eps, mode):
    y = rms(x_rows, final_norm.astype(jnp.float32), eps)
    return mm(y, lm_head.astype(jnp.float32), mode)


def logits_at(flat, dims, tokens, rows, mode="fp32", pad_to=0):
    xr = dense.hidden_at(flat, dims, tokens, rows, mode, pad_to)
    return np.asarray(_head(xr, flat["final_norm"], flat["lm_head"],
                            dims["norm_eps"], mode))
