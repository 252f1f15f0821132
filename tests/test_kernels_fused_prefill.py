"""Fused dequant+chunk-prefill kernel vs dequantize-then-reference
oracle, plus a hypothesis property bounding the KIVI quantize->
dequantize roundtrip error per group (the bound the kernel's in-VREG
dequant inherits)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_prefill import kernel as fk
from repro.kernels.fused_prefill import ops as fops
from repro.kernels.fused_prefill import ref as fr
from repro.kernels.kivi import ref as kr

RNG = np.random.RandomState(3)

# accumulated dequant + flash-vs-dense softmax reassociation error grows
# as codes coarsen (2-bit scales are the largest)
ATOL = {2: 5e-4, 4: 2e-4, 8: 1e-4}


def build_planes(P, T, C, hd, bits, kg, vg):
    q = jnp.asarray(RNG.randn(P, C, hd).astype(np.float32))
    kc = jnp.asarray(RNG.randn(P, C, hd).astype(np.float32))
    vc = jnp.asarray(RNG.randn(P, C, hd).astype(np.float32))
    packs = {k: [] for k in ("kp", "ks", "kz", "vp", "vs", "vz")}
    quants = []
    for _ in range(P):
        k = jnp.asarray(RNG.randn(T, hd).astype(np.float32))
        v = jnp.asarray(RNG.randn(T, hd).astype(np.float32))
        kq = kr.quantize_ref(k, bits, kg, 0)
        vq = kr.quantize_ref(v, bits, vg, 1)
        packs["kp"].append(kq.packed); packs["ks"].append(kq.scale)
        packs["kz"].append(kq.zero); packs["vp"].append(vq.packed)
        packs["vs"].append(vq.scale); packs["vz"].append(vq.zero)
        quants.append((kq, vq))
    return q, kc, vc, {k: jnp.stack(v) for k, v in packs.items()}, quants


def run_fused(q, kc, vc, packs, cur, *, bits, kg, vg, tb):
    return fk.fused_chunk_prefill(
        q, packs["kp"], packs["ks"], packs["kz"],
        packs["vp"], packs["vs"], packs["vz"], kc, vc, cur,
        bits=bits, k_group=kg, v_group=vg, tb=tb, interpret=True)


@pytest.mark.slow            # Pallas interpret-mode sweep
@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("T,tb", [(256, 128), (512, 256)])
def test_fused_chunk_prefill_matches_oracle(bits, T, tb):
    P, C, hd, kg, vg = 2, 32, 128, 64, 64
    q, kc, vc, packs, quants = build_planes(P, T, C, hd, bits, kg, vg)
    cur = jnp.asarray(RNG.randint(1, T + 1, (P, 1)), jnp.int32)
    out = run_fused(q, kc, vc, packs, cur, bits=bits, kg=kg, vg=vg, tb=tb)
    for p in range(P):
        ref = fr.chunk_prefill_quantized_ref(q[p], quants[p][0],
                                             quants[p][1], kc[p], vc[p],
                                             cur[p, 0])
        np.testing.assert_allclose(np.asarray(out[p]), np.asarray(ref),
                                   rtol=1e-4, atol=ATOL[bits])


@pytest.mark.slow
def test_masking_excludes_prefix_tail_and_chunk_future():
    """Prefix entries past cur_len and chunk entries after the query
    position must not affect the output."""
    P, T, C, hd, bits, kg, vg = 1, 256, 32, 128, 4, 64, 64
    q, kc, vc, packs, _ = build_planes(P, T, C, hd, bits, kg, vg)
    cur = jnp.asarray([[100]], jnp.int32)
    out1 = run_fused(q, kc, vc, packs, cur, bits=bits, kg=kg, vg=vg, tb=128)
    # corrupt the prefix beyond cur_len
    packs2 = dict(packs, vp=packs["vp"].at[:, 200:].set(255))
    out2 = run_fused(q, kc, vc, packs2, cur, bits=bits, kg=kg, vg=vg,
                     tb=128)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
    # corrupt the chunk's LAST key/value: only the last query row sees it
    kc3 = kc.at[:, -1].set(7.0)
    vc3 = vc.at[:, -1].set(7.0)
    out3 = run_fused(q, kc3, vc3, packs, cur, bits=bits, kg=kg, vg=vg,
                     tb=128)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                               np.asarray(out3[:, :-1]))
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out3[:, -1]))


@pytest.mark.slow
def test_ops_plane_wrapper_matches_kernel():
    """The jit dispatch wrapper (jnp fallback on CPU) agrees with the
    interpret-mode kernel and the oracle."""
    P, T, C, hd, bits, kg, vg = 3, 256, 32, 128, 4, 64, 64
    q, kc, vc, packs, quants = build_planes(P, T, C, hd, bits, kg, vg)
    cur = jnp.asarray([[256], [100], [7]], jnp.int32)
    out = fops.chunk_prefill_planes(
        q, packs["kp"], packs["ks"], packs["kz"],
        packs["vp"], packs["vs"], packs["vz"], kc, vc, cur,
        bits=bits, k_group=kg, v_group=vg)
    ker = run_fused(q, kc, vc, packs, cur, bits=bits, kg=kg, vg=vg, tb=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ker),
                               rtol=1e-4, atol=2e-4)
    for p in range(P):
        ref = fr.chunk_prefill_quantized_ref(q[p], quants[p][0],
                                             quants[p][1], kc[p], vc[p],
                                             cur[p, 0])
        np.testing.assert_allclose(np.asarray(out[p]), np.asarray(ref),
                                   rtol=1e-4, atol=2e-4)


def test_quantize_roundtrip_error_bounded_per_group():
    """Property: asymmetric group quantization's roundtrip error is at
    most half a step, where the step is the GROUP's (max-min)/(2^b-1) —
    the bound that makes in-VREG dequant numerically interchangeable
    with the standalone pass."""
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=40)
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([2, 4, 8]),
           st.sampled_from([0, 1]), st.sampled_from([16, 32]))
    def prop(seed, bits, axis, group):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(64, 32).astype(np.float32)
                        * rng.uniform(0.1, 10.0))
        qt = kr.quantize_ref(x, bits, group, axis)
        err = np.abs(np.asarray(kr.dequantize_ref(qt)) - np.asarray(x))
        xg = np.asarray(x).T if axis == 1 else np.asarray(x)
        g = xg.shape[0] // group
        grouped = xg.reshape(g, group, xg.shape[1])
        step = (grouped.max(1) - grouped.min(1)) / (2 ** bits - 1)
        bound = np.repeat(step / 2, group, axis=0) + 1e-5
        errg = err.T if axis == 1 else err
        assert (errg <= bound).all()

    prop()
