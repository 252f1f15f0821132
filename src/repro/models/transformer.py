"""Unified LM: dense / MoE / MLA / SSM / hybrid / enc-dec / VLM backbones.

Layer stacking uses ``lax.scan`` over *block groups* (DESIGN.md §8.2): all
layers of the repeating pattern have their params stacked on a leading
group axis, so HLO size and compile time are O(period), not O(depth) —
jamba's 72 layers lower as one scan over 9 groups of 8.

Heterogeneous prefixes (deepseek's dense first layer) are kept unstacked in
``params["prefix"]``.

Caches (serving):
  attn  : {"k": (B,C,Kv,hd), "v": ...} or MLA {"ckv": (B,C,r), "krope": ...}
  mamba : {"conv": (B,c-1,d_in), "ssm": (B,d_in,n)}
  cross : {"k","v"} over encoder length (enc-dec only)
stacked to (G, ...) per scanned group position, mirroring the param stack.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttnKind, FFNKind, LayerKind, ModelConfig
from repro.launch.sharding import constrain
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import (
    Params, _dtype, cross_entropy_loss, dense_init, init_mlp, init_rmsnorm,
    mlp_fwd, rmsnorm,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _prefix_count(cfg: ModelConfig) -> int:
    """Layers whose pytree structure differs from the scanned stack."""
    if cfg.moe is not None and cfg.moe.first_k_dense > 0:
        return cfg.moe.first_k_dense
    return 0


def init_block(rng, cfg: ModelConfig, layer_idx: int, dtype,
               with_cross: bool = False) -> Params:
    kind = cfg.layer_kinds()[layer_idx]
    ks = jax.random.split(rng, 4)
    p: Params = {"ln1": init_rmsnorm(cfg.d_model, dtype)}
    if kind == LayerKind.MAMBA:
        p["mamba"] = mamba_lib.init_mamba(ks[0], cfg, dtype)
    else:
        p["attn"] = attn_lib.init_attention(ks[0], cfg, dtype)
    if with_cross:
        p["ln_cross"] = init_rmsnorm(cfg.d_model, dtype)
        p["cross"] = attn_lib.init_attention(ks[1], cfg, dtype, cross=True)
    if cfg.ffn_kind != FFNKind.NONE:
        p["ln2"] = init_rmsnorm(cfg.d_model, dtype)
        if cfg.uses_moe_at(layer_idx):
            p["ffn_moe"] = moe_lib.init_moe(ks[2], cfg, dtype)
        else:
            d_ff = cfg.d_ff
            if cfg.moe is not None and layer_idx < cfg.moe.first_k_dense:
                d_ff = cfg.moe.dense_d_ff or cfg.d_ff
            p["ffn"] = init_mlp(ks[2], cfg.d_model, d_ff, dtype)
    return p


def _init_enc_block(rng, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 2)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "attn": attn_lib.init_attention(ks[0], cfg, dtype, cross=True),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "ffn": init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype),
    }


def init_params(rng, cfg: ModelConfig) -> Params:
    dtype = _dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 8)
    p: Params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                    dtype=jnp.float32) * 0.02).astype(dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size, dtype)

    with_cross = cfg.is_encoder_decoder
    npre = _prefix_count(cfg)
    pattern, n_groups = cfg.block_group()
    period = len(pattern)
    # prefix layers come off the top of the layer list; the scanned stack
    # covers layers [npre, npre + n_scan), n_scan = n_layers - npre.
    n_scan = cfg.n_layers - npre
    assert n_scan % period == 0, (cfg.name, n_scan, period)
    n_groups = n_scan // period

    if npre:
        p["prefix"] = [init_block(k, cfg, i, dtype, with_cross)
                       for i, k in enumerate(jax.random.split(ks[2], npre))]
    else:
        p["prefix"] = []

    group_rngs = jax.random.split(ks[3], n_groups)

    def one_group(r):
        rs = jax.random.split(r, period)
        return [init_block(rs[j], cfg, npre + j, dtype, with_cross)
                for j in range(period)]

    p["stack"] = jax.vmap(one_group)(group_rngs)

    if cfg.is_encoder_decoder:
        enc_rngs = jax.random.split(ks[4], cfg.n_enc_layers)
        p["enc_stack"] = jax.vmap(
            lambda r: _init_enc_block(r, cfg, dtype))(enc_rngs)
        p["enc_norm"] = init_rmsnorm(cfg.d_model, dtype)
    if cfg.n_patches:
        p["patch_proj"] = dense_init(ks[5], cfg.d_model, cfg.d_model, dtype)
    return p


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _block_cache(cfg: ModelConfig, kind: LayerKind, batch: int, capacity: int,
                 dtype, enc_len: int = 0) -> Params:
    c: Params = {}
    if kind == LayerKind.MAMBA:
        c["mamba"] = mamba_lib.init_mamba_cache(cfg, batch, dtype)
    else:
        c["self"] = attn_lib.init_cache(cfg, batch, capacity, dtype)
    if cfg.is_encoder_decoder:
        hd = cfg.resolved_head_dim
        c["cross"] = {
            "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
        }
    return c


def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               enc_len: int = 0, kv_bits: int = 16) -> Params:
    """Zeroed decode cache pytree mirroring the param stack layout.

    kv_bits < 16 builds the packed-uint8 quantized cache for GQA attention
    layers (AdaptCache serve_step_quantized; MLA latents and SSM states
    stay full-precision here — their quantization lives in the storage
    tier)."""
    dtype = _dtype(cfg.dtype)
    npre = _prefix_count(cfg)
    pattern, _ = cfg.block_group()
    period = len(pattern)
    n_groups = (cfg.n_layers - npre) // period
    kinds = cfg.layer_kinds()

    def block(kind):
        c = _block_cache(cfg, kind, batch, capacity, dtype, enc_len)
        if kv_bits < 16 and "self" in c and "k" in c["self"] \
                and cfg.attn_kind == AttnKind.GQA:
            c["self"] = attn_lib.init_quantized_cache(cfg, batch, capacity,
                                                      bits=kv_bits)
        return c

    prefix = [block(kinds[i]) for i in range(npre)]
    group = [block(kinds[npre + j]) for j in range(period)]
    stack = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), group)
    return {"prefix": prefix, "stack": stack}


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _apply_block(bp: Params, cfg: ModelConfig, kind: LayerKind, x, positions,
                 cache_j: Optional[Params], cur_index, enc_out,
                 decode: bool,
                 moe_dropless: bool = False) -> Tuple[jax.Array, Params, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    new_cache: Params = {}
    h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
    if kind == LayerKind.MAMBA:
        out, mc = mamba_lib.mamba_fwd(
            bp["mamba"], cfg, h,
            cache=None if cache_j is None else cache_j.get("mamba"),
            decode=decode)
        new_cache["mamba"] = mc
    else:
        cj = None if cache_j is None else cache_j.get("self")
        if decode and cj is not None and "k_packed" in cj:
            # AdaptCache quantized-KV data plane (serve_step_quantized)
            out, ac = attn_lib.attention_fwd_quantized(
                bp["attn"], cfg, h, positions, cj, cur_index)
        else:
            out, ac = attn_lib.attention_fwd(
                bp["attn"], cfg, h, positions, cache=cj,
                cur_index=cur_index)
        new_cache["self"] = ac
    x = x + _scaled(out, cfg.residual_scale)
    x = constrain(x, ("data", None, None))

    if "cross" in bp and enc_out is not None or (
            "cross" in bp and cache_j is not None and "cross" in cache_j):
        h = rmsnorm(x, bp["ln_cross"], cfg.norm_eps)
        ccache = None if cache_j is None else cache_j.get("cross")
        # if cross KV already cached (decode), kv_source is unused
        out, cc = attn_lib.attention_fwd(
            bp["cross"], cfg, h, positions,
            cache=ccache if (ccache is not None and decode) else None,
            kv_source=enc_out if enc_out is not None else jnp.zeros(
                (x.shape[0], 1, cfg.d_model), x.dtype))
        new_cache["cross"] = cc
        x = x + out

    if cfg.ffn_kind != FFNKind.NONE:
        h = rmsnorm(x, bp["ln2"], cfg.norm_eps)
        if "ffn_moe" in bp:
            from repro.launch import sharding as _shlib
            moe_impl = (moe_lib.moe_fwd_ep if _shlib._rules() is not None
                        else moe_lib.moe_fwd)
            out, aux = moe_impl(bp["ffn_moe"], cfg, h,
                                dropless=moe_dropless or decode)
        else:
            out = mlp_fwd(bp["ffn"], h)
        x = x + _scaled(out, cfg.residual_scale)
        x = constrain(x, ("data", None, None))
    return x, new_cache, aux


def _run_stack(params: Params, cfg: ModelConfig, x, positions,
               cache: Optional[Params], cur_index, enc_out,
               decode: bool, remat: bool,
               want_cache: bool = True,
               moe_dropless: bool = False) -> Tuple[jax.Array, Params, jax.Array]:
    npre = _prefix_count(cfg)
    pattern, _ = cfg.block_group()
    period = len(pattern)
    kinds = cfg.layer_kinds()
    aux_total = jnp.zeros((), jnp.float32)

    new_prefix = []
    for i, bp in enumerate(params["prefix"]):
        cj = None if cache is None else cache["prefix"][i]
        x, nc, aux = _apply_block(bp, cfg, kinds[i], x, positions, cj,
                                  cur_index, enc_out, decode, moe_dropless)
        new_prefix.append(nc)
        aux_total = aux_total + aux

    def group_body(carry, xs):
        x, aux_sum = carry
        if cache is None:
            gp, gc = xs, None
        else:
            gp, gc = xs
        new_gc = []
        for j in range(period):
            cj = None if gc is None else gc[j]
            x, ncj, aux = _apply_block(gp[j], cfg, kinds[npre + j], x,
                                       positions, cj, cur_index, enc_out,
                                       decode, moe_dropless)
            new_gc.append(ncj)
            aux_sum = aux_sum + aux
        return (x, aux_sum), (new_gc if want_cache else None)

    body = group_body
    if remat:
        body = jax.checkpoint(
            group_body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    xs = params["stack"] if cache is None else (params["stack"], cache["stack"])
    (x, aux_total), new_stack = jax.lax.scan(body, (x, aux_total), xs)
    return x, {"prefix": new_prefix, "stack": new_stack}, aux_total


# ---------------------------------------------------------------------------
# encoder (enc-dec archs; input = stub frame embeddings)
# ---------------------------------------------------------------------------

def encode(params: Params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, T, d_model) precomputed frontend embeddings (stub)."""
    x = frames.astype(_dtype(cfg.dtype))

    def body(x, bp):
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        # bidirectional self-attention (cross-form params: no rope cache path)
        out, _ = attn_lib.attention_fwd(bp["attn"], cfg, h,
                                        jnp.zeros(x.shape[:2], jnp.int32),
                                        kv_source=h)
        x = x + out
        h = rmsnorm(x, bp["ln2"], cfg.norm_eps)
        x = x + mlp_fwd(bp["ffn"], h)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_stack"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# embeddings and heads
# ---------------------------------------------------------------------------

def _scaled(x: jax.Array, factor: Optional[float]) -> jax.Array:
    """``x * factor``; ``x`` itself where the scaling is off (None), so
    that an off scaling adds no operation to the trace."""
    return x if factor is None else x * factor


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jax.Array):
    """Token embeddings in the compute dtype, times ``scale_emb``: the one
    lookup of prefill, training and decode."""
    x = params["embed"][tokens].astype(_dtype(cfg.dtype))
    return _scaled(x, cfg.scale_emb)


def final_hidden(params: Params, cfg: ModelConfig, x: jax.Array):
    """The last layer's output as the head reads it: the final RMSNorm,
    then divided by ``d_model / dim_model_base``."""
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    div = cfg.head_divisor
    return x if div is None else x / div


def embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    dtype = _dtype(cfg.dtype)
    tok = embed_tokens(params, cfg, batch["tokens"])
    if cfg.n_patches and "patch_embeds" in batch:
        patches = batch["patch_embeds"].astype(dtype) @ params["patch_proj"]
        tok = jnp.concatenate([patches, tok], axis=1)
    return constrain(tok, ("data", None, None))


def lm_logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = final_hidden(params, cfg, x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return constrain(logits, ("data", None, "model"))


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def forward_train(params: Params, cfg: ModelConfig,
                  batch: Dict[str, jax.Array],
                  remat: bool = False,
                  moe_dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced logits over the full sequence. Returns (logits, aux)."""
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["frames"])
    x, _, aux = _run_stack(params, cfg, x, positions, None, None, enc_out,
                           decode=False, remat=remat, want_cache=False,
                           moe_dropless=moe_dropless)
    return lm_logits(params, cfg, x), aux


def _head_matrix(params: Params, cfg: ModelConfig) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def chunked_ce_loss(params: Params, cfg: ModelConfig, x: jax.Array,
                    labels: jax.Array, chunk: int = 512,
                    ignore_index: int = -1) -> jax.Array:
    """Cross-entropy over the vocab WITHOUT materializing (B, S, V) logits.

    The (B,S,d) final hiddens are scanned in sequence chunks; each step
    computes one (B, chunk, V) logits block, reduces it to (nll_sum, count),
    and frees it — peak logits memory drops S/chunk-fold (the difference
    between fitting HBM and not for 1M-token batches x 50k-150k vocabs)."""
    x = final_hidden(params, cfg, x)
    head = _head_matrix(params, cfg)
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)),
                         constant_values=ignore_index)
        s = s + pad
    n = s // chunk
    xc = x.reshape(b, n, chunk, d).swapaxes(0, 1)        # (n, B, c, d)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)

    def body(carry, inp):
        nll_sum, cnt = carry
        x_c, lab_c = inp
        logits = (x_c @ head).astype(jnp.float32)
        logits = constrain(logits, ("data", None, "model"))
        mask = lab_c != ignore_index
        safe = jnp.where(mask, lab_c, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(mask, logz - gold, 0.0)
        return (nll_sum + nll.sum(), cnt + mask.sum()), None

    (nll_sum, cnt), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros((), jnp.int32)), (xc, lc))
    return nll_sum / jnp.maximum(cnt, 1)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            aux_weight: float = 0.01, remat: bool = False,
            loss_chunk: int = 512) -> jax.Array:
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    enc_out = encode(params, cfg, batch["frames"]) if cfg.is_encoder_decoder \
        else None
    x, _, aux = _run_stack(params, cfg, x, positions, None, None, enc_out,
                           decode=False, remat=remat, want_cache=False)
    loss = chunked_ce_loss(params, cfg, x, batch["labels"], chunk=loss_chunk)
    return loss + aux_weight * aux


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            capacity: int, remat: bool = False,
            moe_dropless: bool = True) -> Tuple[jax.Array, Params]:
    """Process the full prompt; return (last-position logits, decode cache).

    Attention K/V produced at native length S are written into zeroed
    capacity-C buffers at offset 0 (C >= S).
    """
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    enc_out = encode(params, cfg, batch["frames"]) if cfg.is_encoder_decoder else None

    x, raw_cache, _ = _run_stack(params, cfg, x, positions, None, None,
                                 enc_out, decode=False, remat=remat,
                                 moe_dropless=moe_dropless)
    logits = lm_logits(params, cfg, x[:, -1:, :])

    full = init_cache(cfg, b, capacity,
                      enc_len=enc_out.shape[1] if enc_out is not None else 0)

    def place(z, n):
        if z.shape == n.shape:      # mamba states / cross KV: exact size
            return n
        return jax.lax.dynamic_update_slice(z, n.astype(z.dtype),
                                            (0,) * z.ndim)

    cache = jax.tree.map(place, full, raw_cache)
    return logits, cache


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                cur_index: jax.Array, tokens: jax.Array,
                position: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Params]:
    """One-token decode. tokens: (B, 1) int32.

    cur_index: cache WRITE SLOT — scalar (aligned batch, the dry-run
    serve_step) or (B,) per-lane (continuous batching / ragged sessions).
    position: optional RoPE position of the new token (defaults to
    cur_index); differs from the slot when the cache holds a token-dropped
    entry (StreamingLLM-compressed KV occupies slots [0, n_kept) while the
    new token's true position is the original sequence length).
    """
    x = embed_tokens(params, cfg, tokens)
    b = tokens.shape[0]
    pos = cur_index if position is None else position
    if jnp.ndim(pos) == 0:
        positions = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1))
    else:
        positions = pos.astype(jnp.int32)[:, None]
    x, new_cache, _ = _run_stack(params, cfg, x, positions, cache, cur_index,
                                 None, decode=True, remat=False)
    return lm_logits(params, cfg, x), new_cache
