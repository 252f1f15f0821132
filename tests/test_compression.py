"""Compression methods: roundtrip, analytic size == actual, semantics."""
import tracemalloc

import ml_dtypes
import numpy as np
import pytest

from repro.core.compression import (
    DropQuantCompression, KIVICompression, NoCompression,
    StreamingLLMCompression, default_registry, kv_nbytes,
)
from repro.core.compression.base import shape_proxy

RNG = np.random.RandomState(4)


def make_kv(L=3, T=128, F=96):
    return {"k": RNG.randn(L, T, F).astype(np.float32),
            "v": RNG.randn(L, T, F).astype(np.float32),
            "positions": np.arange(T, dtype=np.int32)}


def make_ssm():
    return {"ssm": RNG.randn(4, 64, 16).astype(np.float32),
            "conv": RNG.randn(4, 3, 64).astype(np.float32)}


@pytest.mark.parametrize("method_name", ["none", "kivi", "streaming_llm",
                                         "drop_kivi"])
def test_estimate_equals_actual(method_name):
    m = default_registry()[method_name]
    kv = make_kv()
    for rate in m.rates(kv):
        est = m.estimate_nbytes(kv, rate)
        c = m.compress(kv, rate)
        assert c.nbytes == est, (method_name, rate)


def test_kivi_error_bounded_by_scale():
    m = KIVICompression()
    kv = make_kv()
    for rate in m.rates(kv):
        c = m.compress(kv, rate)
        d = m.decompress(c)
        for name in ("k", "v"):
            # elementwise error <= max scale of the quantizer
            smax = np.abs(c.arrays[f"{name}.scale"]).max()
            assert np.abs(d[name] - kv[name]).max() <= smax + 1e-6


def test_kivi_monotone_quality():
    """More bits -> strictly lower reconstruction error."""
    m = KIVICompression()
    kv = make_kv()
    errs = []
    for bits in (8, 4, 2):
        c = m.compress(kv, 0.0, bits=bits)
        d = m.decompress(c)
        errs.append(float(np.abs(d["k"] - kv["k"]).mean()))
    assert errs[0] < errs[1] < errs[2]


def test_streaming_keeps_sinks_and_recents():
    m = StreamingLLMCompression(n_sink=4)
    kv = make_kv(T=128)
    c = m.compress(kv, 0.25)
    pos = c.arrays["positions"]
    assert list(pos[:4]) == [0, 1, 2, 3]
    n_keep = len(pos)
    assert abs(n_keep - 32) <= 1
    assert list(pos[4:]) == list(range(128 - (n_keep - 4), 128))
    d = m.decompress(c)
    assert d["k"].shape[1] == n_keep
    # kept rows are bit-exact (lossless on the kept set)
    np.testing.assert_array_equal(d["k"], kv["k"][:, pos])


def test_streaming_inapplicable_to_ssm():
    m = StreamingLLMCompression()
    assert not m.applicable(make_ssm())
    assert KIVICompression().applicable(make_ssm())


def test_streaming_applicable_to_mla_latent():
    m = StreamingLLMCompression(n_sink=2)
    kv = {"ckv": RNG.randn(3, 64, 32).astype(np.float32),
          "krope": RNG.randn(3, 64, 8).astype(np.float32)}
    assert m.applicable(kv)
    c = m.compress(kv, 0.5)
    d = m.decompress(c)
    assert d["ckv"].shape[1] == len(c.arrays["positions"])


def test_drop_kivi_composes():
    m = DropQuantCompression()
    kv = make_kv(T=128)
    rates = m.rates(kv)
    assert min(rates) < 0.05                     # reaches deep compression
    c = m.compress(kv, min(rates))
    d = m.decompress(c)
    assert d["k"].shape[1] < 128                 # dropped
    assert c.nbytes < 0.06 * kv_nbytes(kv)


# (L, T, F, dtype, positions, names): T around n_sink + 1 (n_sink 4), T off
# the KIVI group (64), F under the group, half precisions, MLA latents
DROP_KIVI_SHAPES = [
    (2, 4, 64, np.float32, True, ("k", "v")),
    (2, 5, 64, np.float32, False, ("k", "v")),
    (2, 6, 64, np.float16, True, ("k", "v")),
    (3, 17, 32, np.float32, True, ("k", "v")),
    (1, 100, 96, ml_dtypes.bfloat16, True, ("k", "v")),
    (2, 130, 48, np.float16, False, ("k", "v")),
    (4, 256, 128, ml_dtypes.bfloat16, False, ("k", "v")),
    (3, 100, 32, np.float32, True, ("ckv", "krope")),
    (2, 65, 64, ml_dtypes.bfloat16, False, ("ckv", "krope")),
]


@pytest.mark.parametrize("L,T,F,dtype,positions,names", DROP_KIVI_SHAPES)
def test_drop_kivi_estimate_from_shapes(L, T, F, dtype, positions, names):
    """The shape-only estimate is the compressed size of the real arrays,
    and a shape proxy prices the ladder exactly as the arrays do."""
    m = DropQuantCompression()
    widths = {"krope": 16}
    kv = {n: RNG.randn(L, T, widths.get(n, F)).astype(dtype) for n in names}
    if positions:
        kv["positions"] = np.arange(T, dtype=np.int32)
    rates = m.rates(kv)
    assert m.rates(shape_proxy(kv)) == rates
    for rate in rates:
        est = m.estimate_nbytes(shape_proxy(kv), rate)
        assert est == m.estimate_nbytes(kv, rate)
        assert est == m.compress(kv, rate).nbytes, rate


def test_drop_kivi_pricing_copies_no_tokens():
    """Pricing a full-width page's ladder from its shape proxy allocates
    nothing token-sized (one copy of k would be 29 MB)."""
    m = DropQuantCompression()
    proxy = shape_proxy({"k": np.zeros((28, 256, 1024), np.float32),
                         "v": np.zeros((28, 256, 1024), np.float32),
                         "positions": np.arange(256, dtype=np.int32)})
    tracemalloc.start()
    try:
        for rate in m.rates(proxy):
            m.estimate_nbytes(proxy, rate)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 1 << 20, peak


def test_ssm_quant_roundtrip():
    m = KIVICompression()
    ssm = make_ssm()
    c = m.compress(ssm, 0.0, bits=8)
    d = m.decompress(c)
    assert d["ssm"].shape == ssm["ssm"].shape
    assert np.abs(d["ssm"] - ssm["ssm"]).max() < 0.05


def test_serialization_roundtrip():
    from repro.core.compression.base import CompressedEntry
    m = KIVICompression()
    kv = make_kv()
    c = m.compress(kv, 0.2)
    raw = c.tobytes()
    c2 = CompressedEntry.frombytes(raw, c.method, c.rate, c.meta)
    for k in c.arrays:
        np.testing.assert_array_equal(c.arrays[k], c2.arrays[k])
