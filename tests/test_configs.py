import pytest

from repro.configs import (
    ASSIGNED, REGISTRY, SHAPES, get_config, get_shape, shape_applicable,
)
from repro.configs.base import AttnKind, FFNKind, LayerKind


def test_all_assigned_present():
    assert len(ASSIGNED) == 10
    for name in ASSIGNED:
        assert name in REGISTRY


EXPECTED = {
    # (layers, d_model, heads, kv, d_ff, vocab)
    "stablelm-3b": (32, 2560, 32, 32, 6912, 50304),
    "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
    "smollm-135m": (30, 576, 9, 3, 1536, 49152),
    "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
    "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
    "deepseek-v2-lite-16b": (27, 2048, 16, 16, 1408, 102400),
    "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
    "falcon-mamba-7b": (64, 4096, 1, 1, 0, 65024),
    "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
    "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
}


@pytest.mark.parametrize("name", ASSIGNED)
def test_exact_assigned_numbers(name):
    c = get_config(name)
    exp = EXPECTED[name]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == exp


def test_special_features():
    assert get_config("qwen3-1.7b").qk_norm
    assert get_config("stablelm-3b").rotary_pct == 0.25
    ds = get_config("deepseek-v2-lite-16b")
    assert ds.attn_kind == AttnKind.MLA and ds.mla.kv_lora_rank == 512
    assert ds.moe.n_routed_experts == 64 and ds.moe.top_k == 6
    assert ds.moe.n_shared_experts == 2 and ds.moe.first_k_dense == 1
    ol = get_config("olmoe-1b-7b")
    assert ol.moe.n_routed_experts == 64 and ol.moe.top_k == 8
    fm = get_config("falcon-mamba-7b")
    assert fm.primary_kind == LayerKind.MAMBA and fm.ssm.d_state == 16
    assert fm.ffn_kind == FFNKind.NONE
    sm = get_config("seamless-m4t-large-v2")
    assert sm.is_encoder_decoder and sm.n_enc_layers == 24
    jb = get_config("jamba-1.5-large-398b")
    assert jb.attn_period == 8 and jb.moe.n_routed_experts == 16
    kinds = jb.layer_kinds()
    assert sum(k == LayerKind.ATTN for k in kinds) == 9   # 1:7 interleave


def test_jamba_moe_every_other_layer():
    jb = get_config("jamba-1.5-large-398b")
    flags = [jb.uses_moe_at(i) for i in range(8)]
    assert sum(flags) == 4


def test_shapes_and_applicability():
    assert [s.name for s in SHAPES] == ["train_4k", "prefill_32k",
                                        "decode_32k", "long_500k"]
    long = get_shape("long_500k")
    ok, _ = shape_applicable(get_config("qwen3-1.7b"), long)
    assert not ok                              # pure full-attention: skip
    ok, _ = shape_applicable(get_config("falcon-mamba-7b"), long)
    assert ok
    ok, _ = shape_applicable(get_config("jamba-1.5-large-398b"), long)
    assert ok


@pytest.mark.parametrize("name", ASSIGNED)
def test_smoke_variants_preserve_structure(name):
    full = get_config(name)
    sm = get_config(name, smoke=True)
    assert sm.family == full.family
    assert sm.attn_kind == full.attn_kind
    assert sm.ffn_kind == full.ffn_kind
    assert (sm.moe is None) == (full.moe is None)
    assert (sm.ssm is None) == (full.ssm is None)
    assert sm.is_encoder_decoder == full.is_encoder_decoder
    if full.attn_period > 1:
        assert sm.attn_period == full.attn_period
    assert sm.vocab_size <= 512 and sm.d_model <= 128


def test_kv_bytes_per_token():
    # mamba has no KV; MLA stores latent only
    assert get_config("falcon-mamba-7b").kv_bytes_per_token() == 0
    ds = get_config("deepseek-v2-lite-16b")
    assert ds.kv_bytes_per_token() == 27 * (512 + 64) * 2
    q = get_config("qwen3-1.7b")
    assert q.kv_bytes_per_token() == 28 * 2 * 8 * 128 * 2


def test_minicpm_mup_scalings():
    c = get_config("minicpm-2b")
    assert (c.scale_emb, c.scale_depth, c.dim_model_base) == (12, 1.4, 256)
    assert (c.norm_eps, c.rope_theta, c.tie_embeddings) == (1e-5, 1e4, True)
    assert c.residual_scale == pytest.approx(1.4 / 40 ** 0.5)
    assert c.head_divisor == 9.0
    assert c.kv_bytes_per_token() == 40 * 2 * 36 * 64 * 2 == 368_640
    q = get_config("qwen3-1.7b")
    assert q.residual_scale is None and q.head_divisor is None
