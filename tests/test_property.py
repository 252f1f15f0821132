"""Hypothesis property tests on system invariants."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compression import (
    KIVICompression, StreamingLLMCompression, kv_nbytes,
)
from repro.serving.metrics import rouge_l, token_f1

RNG = np.random.RandomState(6)


@given(bits=st.sampled_from([2, 4, 8]),
       t=st.integers(16, 160), f=st.integers(8, 96),
       scale=st.floats(0.01, 100.0))
@settings(max_examples=25, deadline=None)
def test_kivi_error_bound_property(bits, t, f, scale):
    """For any shape/scale, reconstruction error <= quantizer step."""
    kv = {"k": (RNG.randn(1, t, f) * scale).astype(np.float32),
          "v": (RNG.randn(1, t, f) * scale).astype(np.float32)}
    m = KIVICompression(group_size=32)
    c = m.compress(kv, 0.0, bits=bits)
    d = m.decompress(c)
    for name in ("k", "v"):
        smax = np.abs(c.arrays[f"{name}.scale"]).max()
        assert np.abs(d[name] - kv[name]).max() <= smax * 1.001 + 1e-6


@given(t=st.integers(12, 300), keep=st.sampled_from([1.0, 0.5, 0.25, 0.125]))
@settings(max_examples=25, deadline=None)
def test_streaming_invariants(t, keep):
    kv = {"k": RNG.randn(2, t, 16).astype(np.float32),
          "v": RNG.randn(2, t, 16).astype(np.float32)}
    m = StreamingLLMCompression(n_sink=4)
    c = m.compress(kv, keep)
    pos = c.arrays["positions"]
    # kept positions strictly increasing, within range, sinks first
    assert (np.diff(pos) > 0).all()
    assert pos[0] == 0 and pos[-1] == t - 1 or keep == 1.0 or t <= 5
    assert pos.max() < t
    # size never increases, monotone in keep
    assert c.nbytes <= kv_nbytes(kv) + 4 * t


@given(a=st.lists(st.integers(0, 30), max_size=20),
       b=st.lists(st.integers(0, 30), max_size=20))
@settings(max_examples=50, deadline=None)
def test_metric_properties(a, b):
    for fn in (token_f1, rouge_l):
        s = fn(a, b)
        assert 0.0 <= s <= 1.0
        assert fn(a, b) == fn(b, a) or fn is token_f1  # f1 symmetric too
        if a == b:
            assert s == 1.0


@given(freq=st.floats(0.001, 10), quality=st.floats(0, 1),
       nbytes=st.integers(1, 10**9), alpha=st.floats(0.0001, 10))
@settings(max_examples=50, deadline=None)
def test_utility_monotonicity(freq, quality, nbytes, alpha):
    """Utility increases with freq*quality, decreases with size."""
    bw = 1e9
    u = freq * (alpha * quality - nbytes / bw)
    u_better_q = freq * (alpha * min(1.0, quality + 0.1) - nbytes / bw)
    u_bigger = freq * (alpha * quality - (nbytes * 2) / bw)
    assert u_better_q >= u
    assert u_bigger <= u


@given(step=st.integers(0, 500))
@settings(max_examples=30, deadline=None)
def test_wsd_schedule_bounds(step):
    import jax.numpy as jnp
    from repro.training.optimizer import wsd_schedule
    lr = wsd_schedule(1.0, 50, 200, 100)
    v = float(lr(jnp.int32(step)))
    assert 0.0 <= v <= 1.0 + 1e-6


@given(t=st.integers(1, 220), page=st.sampled_from([16, 32, 64, 128]),
       layers=st.integers(1, 3), feat=st.sampled_from([4, 8]),
       with_state=st.booleans())
@settings(max_examples=40, deadline=None)
def test_split_join_roundtrip_property(t, page, layers, feat, with_state):
    """For any length/page size, join(split(kv) pages + remainder)
    reconstructs the entry EXACTLY: token arrays and positions in
    order, and SSM state (which only lives in the remainder) intact."""
    from repro.serving.chunking import join_kv, split_kv
    kv = {"k": RNG.randn(layers, t, feat).astype(np.float32),
          "v": RNG.randn(layers, t, feat).astype(np.float32),
          "positions": np.arange(t, dtype=np.int32)}
    if with_state:
        kv["ssm"] = RNG.randn(layers, 4, 4).astype(np.float32)
        kv["conv"] = RNG.randn(layers, 3, 4).astype(np.float32)
    pages, rem = split_kv(kv, page)
    assert len(pages) == t // page
    assert all(p["k"].shape[1] == page for p in pages)
    assert rem["k"].shape[1] == t - page * (t // page)
    # state is never paged: it rides the remainder only
    assert all("ssm" not in p and "conv" not in p for p in pages)
    rebuilt = join_kv(pages + [rem])
    assert set(rebuilt) == set(kv)
    for name, a in kv.items():
        np.testing.assert_array_equal(rebuilt[name], a)


@given(quals=st.lists(st.floats(0.0, 1.0), min_size=1, max_size=12),
       idx=st.integers(0, 11), new_rate=st.floats(0.0, 1.0),
       page_tokens=st.integers(1, 128), rem_tokens=st.integers(0, 127))
@settings(max_examples=60, deadline=None)
def test_composed_quality_monotone_in_any_page_rate(quals, idx, new_rate,
                                                    page_tokens, rem_tokens):
    """Composed run quality is monotone non-increasing when any single
    page's compression rate decreases (through a monotone quality-rate
    curve), stays in [0, 1], and equals the per-page score on uniform
    runs. The weighting (full pages + a sub-page remainder) must not
    break monotonicity."""
    from repro.core.estimator import QualityEstimator
    qe = QualityEstimator()
    # monotone non-decreasing synthetic curve: lower rate -> lower quality
    qe.set_curve("qa", "kivi", [(0.0, 0.0), (0.25, 0.5), (1.0, 1.0)])
    idx = idx % len(quals)
    weights = [page_tokens] * len(quals)
    if rem_tokens:
        weights[-1] = rem_tokens        # last piece is the remainder
    base = QualityEstimator.compose(quals, weights)
    assert 0.0 <= base <= 1.0
    # uniform run keeps the per-page score
    u = QualityEstimator.compose([quals[idx]] * len(quals))
    assert u == pytest.approx(quals[idx], abs=1e-9)
    # drop one page's quality through the monotone curve: the composed
    # score must not increase
    old_q = qe.predict("qa", "kivi", 1.0, redundancy=0.5)
    new_q = qe.predict("qa", "kivi", new_rate, redundancy=0.5)
    assert new_q <= old_q + 1e-12
    lowered = list(quals)
    lowered[idx] = min(lowered[idx], new_q)
    assert (QualityEstimator.compose(lowered, weights) <= base + 1e-12)
    # a zero-quality weighted piece zeroes the composition
    zeroed = list(quals)
    zeroed[idx] = 0.0
    if weights[idx] > 0:
        assert QualityEstimator.compose(zeroed, weights) == 0.0


@given(n=st.integers(16, 2048))
@settings(max_examples=20, deadline=None)
def test_q8_codec_roundtrip_bound(n):
    import jax.numpy as jnp
    from repro.training.optimizer import _q8_decode, _q8_encode
    x = jnp.asarray(RNG.randn(n).astype(np.float32))
    q, s = _q8_encode(x)
    y = _q8_decode(q, s, (n,), np.float32)
    # blockwise absmax: error <= scale/2 per element approx (<= scale)
    step = np.repeat(np.asarray(s)[:, 0], 64)[:n]
    assert (np.abs(np.asarray(y) - np.asarray(x)) <= step + 1e-7).all()
