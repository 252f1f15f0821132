"""ModelRunner: bridges the storage-layer KVData format and the model's
decode-cache pytree, and runs prefill / greedy generation.

KVData layout (batch squeezed, numpy, storage-friendly):
  GQA :  {"k": (L_attn, T, Kv*hd), "v": (L_attn, T, Kv*hd)}
  MLA :  {"ckv": (L_attn, T, r), "krope": (L_attn, T, rope_d)}
  SSM :  {"ssm": (L_m, d_in, n), "conv": (L_m, c-1, d_in)}   (+ attention
         arrays for hybrids)
  always: {"positions": (T_kept,)} after token dropping.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnKind, LayerKind, ModelConfig
from repro.core.compression.base import KVData
from repro.models import Model
from repro.models.transformer import _prefix_count
from repro.runtime.spans import span


def _layer_cache_refs(cache, cfg: ModelConfig):
    """Yield (layer_idx, kind, getter, setter) for every layer's block cache.

    getter() returns the per-layer block-cache dict with batch leading
    (stack leaves are indexed at their group position); setter(new) writes
    a modified dict back (functionally, returning a new cache pytree is the
    caller's job — we mutate a python-level copy of the container lists)."""
    npre = _prefix_count(cfg)
    period = len(cfg.block_group()[0])
    kinds = cfg.layer_kinds()
    for i, kind in enumerate(kinds):
        if i < npre:
            yield i, kind, ("prefix", i, None)
        else:
            g, j = divmod(i - npre, period)
            yield i, kind, ("stack", j, g)


def cache_to_kvdata(cache, cfg: ModelConfig, n_tokens: int) -> KVData:
    """Extract a storable KVData from a (batch=1) cache pytree."""
    ks, vs, ckvs, kropes, ssms, convs = [], [], [], [], [], []
    for i, kind, (sect, j, g) in _layer_cache_refs(cache, cfg):
        blk = cache[sect][j]
        take = (lambda a: np.asarray(a[g, 0]) if g is not None
                else np.asarray(a[0]))
        if kind == LayerKind.MAMBA:
            ssms.append(take(blk["mamba"]["ssm"]))
            convs.append(take(blk["mamba"]["conv"]))
        elif cfg.attn_kind == AttnKind.MLA:
            ckvs.append(take(blk["self"]["ckv"])[:n_tokens])
            kropes.append(take(blk["self"]["krope"])[:n_tokens])
        else:
            k = take(blk["self"]["k"])[:n_tokens]
            v = take(blk["self"]["v"])[:n_tokens]
            ks.append(k.reshape(n_tokens, -1))
            vs.append(v.reshape(n_tokens, -1))
    out: KVData = {}
    if ks:
        out["k"] = np.stack(ks).astype(np.float32)
        out["v"] = np.stack(vs).astype(np.float32)
    if ckvs:
        out["ckv"] = np.stack(ckvs).astype(np.float32)
        out["krope"] = np.stack(kropes).astype(np.float32)
    if ssms:
        out["ssm"] = np.stack(ssms).astype(np.float32)
        out["conv"] = np.stack(convs).astype(np.float32)
    out["positions"] = np.arange(n_tokens, dtype=np.int32)
    return out


def kvdata_to_cache(kv: KVData, cfg: ModelConfig, model: Model,
                    capacity: int) -> Tuple[dict, int]:
    """Build a capacity-C batch=1 cache pytree from stored KVData.

    Returns (cache, n_kept) — kept rows occupy slots [0, n_kept)."""
    n_kept = int(kv["positions"].shape[0]) if "positions" in kv else (
        kv["k"].shape[1] if "k" in kv else 0)
    cache = model.init_cache(batch=1, capacity=capacity)
    cache = jax.tree.map(lambda x: np.array(x), cache)   # mutable host copy
    ai = mi = 0
    hd = cfg.resolved_head_dim
    for i, kind, (sect, j, g) in _layer_cache_refs(cache, cfg):
        blk = cache[sect][j]

        def put(ref, value):
            if g is not None:
                ref[g, 0, :value.shape[0]] = value
            else:
                ref[0, :value.shape[0]] = value

        if kind == LayerKind.MAMBA:
            def put_full(ref, value):
                if g is not None:
                    ref[g, 0] = value
                else:
                    ref[0] = value
            put_full(blk["mamba"]["ssm"], kv["ssm"][mi])
            put_full(blk["mamba"]["conv"], kv["conv"][mi])
            mi += 1
        elif cfg.attn_kind == AttnKind.MLA:
            put(blk["self"]["ckv"], kv["ckv"][ai])
            put(blk["self"]["krope"], kv["krope"][ai])
            ai += 1
        else:
            put(blk["self"]["k"], kv["k"][ai].reshape(n_kept, -1, hd))
            put(blk["self"]["v"], kv["v"][ai].reshape(n_kept, -1, hd))
            ai += 1
    cache = jax.tree.map(jnp.asarray, cache)
    return cache, n_kept


@dataclasses.dataclass
class ModelRunner:
    model: Model
    params: dict
    capacity: int = 1024

    def __post_init__(self):
        cfg = self.model.cfg
        self._decode = jax.jit(
            lambda p, c, ci, t, pos: self.model.decode_step(p, c, ci, t, pos))

    # -- prefill -> storable entry -------------------------------------------
    def prefill_entry(self, ctx_tokens: np.ndarray) -> KVData:
        t = len(ctx_tokens)
        with span("prefill", tokens=t):
            batch = {"tokens": jnp.asarray(ctx_tokens, jnp.int32)[None]}
            _, cache = self.model.prefill(self.params, batch,
                                          capacity=self.capacity)
            return cache_to_kvdata(cache, self.model.cfg, t)

    # -- generation ------------------------------------------------------------
    def generate_from_kvdata(self, kv: KVData, orig_len: int,
                             question: np.ndarray, max_new: int) -> List[int]:
        cache, n_kept = kvdata_to_cache(kv, self.model.cfg, self.model,
                                        self.capacity)
        toks = list(np.asarray(question, np.int64))
        out: List[int] = []
        slot, pos = n_kept, orig_len
        logits = None
        for step in range(len(toks) + max_new):
            if step < len(toks):
                nxt = int(toks[step])
            else:
                nxt = int(jnp.argmax(logits[0, -1]))
                out.append(nxt)
            if slot >= self.capacity:
                break
            logits, cache = self._decode(
                self.params, cache, jnp.int32(slot),
                jnp.asarray([[nxt]], jnp.int32), jnp.int32(pos))
            slot += 1
            pos += 1
        return out

    def generate_uncompressed(self, ctx_tokens: np.ndarray,
                              question: np.ndarray, max_new: int
                              ) -> Tuple[List[int], KVData]:
        kv = self.prefill_entry(ctx_tokens)
        ans = self.generate_from_kvdata(kv, len(ctx_tokens), question, max_new)
        return ans, kv
