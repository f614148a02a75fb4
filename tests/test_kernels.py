"""Pallas kernel tests: shape/dtype sweeps against the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.distributed.wire import QuantWire
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.quant import (
    dequantize_2d,
    quantize_2d,
    quantize_pack_2d,
    unpack_dequant_2d,
    unpack_dequant_axpy,
    unpack_dequant_axpy_2d,
)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (64, 1024), (1024, 128), (9, 128), (3, 256)])
def test_quant_kernel_matches_ref_exactly(bits, shape):
    """Kernel codes/scales must equal the oracle bit-for-bit (same hash, same seed)."""
    x = jax.random.normal(jax.random.key(42), shape, dtype=jnp.float32) * 3.0
    seed = jnp.asarray([1234], dtype=jnp.uint32)
    codes_k, scale_k = quantize_2d(x, seed, bits=bits, interpret=True)
    codes_r, scale_r = kref.quantize_2d_ref(x, seed, bits=bits)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))
    np.testing.assert_allclose(np.asarray(scale_k), np.asarray(scale_r), rtol=1e-7)


@pytest.mark.parametrize("bits", [4, 8])
def test_dequant_kernel_matches_ref(bits):
    x = jax.random.normal(jax.random.key(0), (32, 256)) * 0.5
    seed = jnp.asarray([7], dtype=jnp.uint32)
    codes, scale = kref.quantize_2d_ref(x, seed, bits=bits)
    out_k = dequantize_2d(codes, scale, bits=bits, interpret=True)
    out_r = kref.dequantize_2d_ref(codes, scale, bits=bits)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(100,), (5, 7, 11), (2048,), (1, 1)])
def test_ops_roundtrip_any_shape(dtype, shape):
    x = (jax.random.normal(jax.random.key(1), shape) * 2).astype(dtype)
    payload = kops.quantize(jax.random.key(2), x, bits=8, block_size=128)
    out = kops.dequantize(payload, bits=8, shape=shape, dtype=dtype)
    assert out.shape == shape and out.dtype == dtype
    bin_w = float(np.asarray(payload["scale"]).max()) / 127
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - x.astype(jnp.float32)))) <= bin_w * 1.01 + 1e-6


def test_ops_unbiased_statistically():
    x = jax.random.normal(jax.random.key(3), (512,))
    acc = jnp.zeros_like(x)
    n = 250          # tolerance below scales with 1/sqrt(n); margin is ~3x
    for k in jax.random.split(jax.random.key(4), n):
        p = kops.quantize(k, x, bits=4, block_size=128)
        acc = acc + kops.dequantize(p, bits=4, shape=x.shape)
    mean = acc / n
    bin_w = 1.0 / 7  # levels for 4 bits
    tol = 6 * bin_w * float(jnp.abs(x).max()) / np.sqrt(n) + 1e-3
    assert float(jnp.max(jnp.abs(mean - x))) < 3 * tol


def test_kernel_payload_compatible_with_compressor():
    """RandomQuantizer(use_kernel=True) must roundtrip via the shared wire format."""
    from repro.core.compression import RandomQuantizer

    comp = RandomQuantizer(bits=8, block_size=128, use_kernel=True)
    x = jax.random.normal(jax.random.key(5), (300,))
    out = comp(jax.random.key(6), x)
    assert out.shape == x.shape
    assert float(jnp.max(jnp.abs(out - x))) < 0.2  # within a few bins


@settings(max_examples=6, deadline=None)
@given(
    rows=st.sampled_from([1, 9, 120, 300]),   # fixed set: padded-shape reuse
    cols=st.sampled_from([128, 256]),
    bits=st.integers(2, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_kernel_property_sweep(rows, cols, bits, seed):
    """Property: kernel == oracle for arbitrary row counts (incl. padding path)."""
    x = jax.random.normal(jax.random.key(seed), (rows, cols)) * 10
    s = jnp.asarray([seed], dtype=jnp.uint32)
    ck, sk = quantize_2d(x, s, bits=bits, interpret=True)
    cr, sr = kref.quantize_2d_ref(x, s, bits=bits)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-7)
    assert ck.shape == (rows, cols) and sk.shape == (rows, 1)


# ------------------------------------------------------- packed wire format


@pytest.mark.parametrize("bits", [2, 4])
def test_pack_unpack_roundtrip_all_code_values(bits):
    """Every representable code survives pack -> unpack exactly."""
    levels = 2 ** (bits - 1) - 1
    cpw = 32 // bits
    vals = np.arange(-levels, levels + 1, dtype=np.int8)
    # tile them through every position within a word (and a few words)
    cols = 4 * cpw
    codes = jnp.asarray(np.resize(vals, (3, cols)))
    packed = kref.pack_codes(codes, bits=bits)
    assert packed.dtype == jnp.uint32 and packed.shape == (3, cols // cpw)
    np.testing.assert_array_equal(
        np.asarray(kref.unpack_codes(packed, bits=bits)), np.asarray(codes))


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("shape", [(8, 128), (64, 1024), (9, 128), (3, 256), (1, 128)])
def test_quant_pack_kernel_matches_ref_exactly(bits, shape):
    """Fused quantize+pack kernel words == oracle words, bit-for-bit; unpacking
    them recovers exactly the codes of the unpacked kernel (lossless)."""
    x = jax.random.normal(jax.random.key(7), shape, dtype=jnp.float32) * 2.0
    seed = jnp.asarray([99], dtype=jnp.uint32)
    pk, sk = quantize_pack_2d(x, seed, bits=bits, interpret=True)
    pr, sr = kref.quantize_pack_2d_ref(x, seed, bits=bits)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-7)
    codes, _ = quantize_2d(x, seed, bits=bits, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(kref.unpack_codes(pk, bits=bits)), np.asarray(codes))


@pytest.mark.parametrize("bits", [2, 4])
def test_unpack_dequant_kernels_match_ref(bits):
    x = jax.random.normal(jax.random.key(1), (37, 256)) * 0.7
    seed = jnp.asarray([5], dtype=jnp.uint32)
    packed, scale = kref.quantize_pack_2d_ref(x, seed, bits=bits)
    out_k = unpack_dequant_2d(packed, scale, bits=bits, interpret=True)
    out_r = kref.unpack_dequant_2d_ref(packed, scale, bits=bits)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-6)

    acc = jax.random.normal(jax.random.key(2), x.shape)
    ax_k = unpack_dequant_axpy_2d(packed, scale, acc, bits=bits, weight=1 / 3,
                                  interpret=True)
    ax_r = kref.unpack_dequant_axpy_2d_ref(packed, scale, acc, bits=bits, weight=1 / 3)
    np.testing.assert_allclose(np.asarray(ax_k), np.asarray(ax_r),
                               rtol=1e-5, atol=1e-6)


def _folded_axpy(codes, scale, acc, *, bits, weight, acc_weight):
    """The decode-axpy through a (rows * nblk, block) fold of the leaf: pad
    the last dim to whole blocks, one kernel row per block, unfold."""
    block = codes.shape[-1] * 32 // bits
    nblk, lead, last = codes.shape[-2], acc.shape[:-1], acc.shape[-1]
    accf = jnp.pad(acc, [(0, 0)] * len(lead) + [(0, nblk * block - last)])
    rows = int(np.prod(lead)) * nblk
    args = (codes.reshape(rows, -1), scale.reshape(rows, 1), accf.reshape(rows, block))
    out = unpack_dequant_axpy_2d(*args, bits=bits, weight=weight,
                                 acc_weight=acc_weight, interpret=True)
    ref = kref.unpack_dequant_axpy_2d_ref(*args, bits=bits, weight=weight,
                                          acc_weight=acc_weight)
    unfold = lambda o: o.reshape(*lead, nblk * block)[..., :last]  # noqa: E731
    return unfold(out), unfold(ref)


@pytest.mark.parametrize("bits,shape,traced", [
    (4, (16, 1024), False),          # nblk == 1: the folded view itself
    (4, (2, 3, 8, 4096), False),     # nblk 4 on a stacked 4-D leaf
    (4, (2, 8, 1280), False),        # a partial last block: 1280 = 1024 + 256
    (4, (24, 2048), False),          # a 2-D leaf
    (4, (2, 8, 3072), True),         # traced weight and acc_weight (ECD)
    (3, (2, 8, 2560), False),        # 3-bit: codes straddle words; partial block
    (4, (2, 8, 11520), False),       # nblk 12: codes fetched 8 blocks, then 4
], ids=["nblk1", "stacked4d", "partial", "2d", "traced", "3bit", "groups"])
def test_rows_view_decode_axpy_matches_folded_and_oracle(bits, shape, traced):
    """The decode-axpy over the leaf's own (rows, last) view equals the fold
    into (rows * nblk, block) bit for bit, and the kernels/ref.py oracle to
    its rounding (the oracle multiplies the weight in last)."""
    x = jax.random.normal(jax.random.key(11), shape) * 2.0
    acc = jax.random.normal(jax.random.key(12), shape)
    payload = QuantWire(bits=bits, block=1024).encode(x, jnp.uint32(4321))
    codes, scale = payload["codes"], payload["scale"]
    rows, (nblk, w) = int(np.prod(shape[:-1])), codes.shape[-2:]

    def rows_view(c, s, a, wt, aw):
        out = unpack_dequant_axpy(c.reshape(rows, nblk, w), s.reshape(rows, nblk),
                                  a.reshape(rows, shape[-1]), bits=bits, weight=wt,
                                  acc_weight=aw, interpret=True)
        return out.reshape(shape)

    weights = (jnp.float32(0.4), jnp.float32(0.6)) if traced else (1 / 3, 1.0)
    if traced:
        got = jax.jit(rows_view)(codes, scale, acc, *weights)
    else:
        got = rows_view(codes, scale, acc, *weights)
    folded, oracle = _folded_axpy(codes, scale, acc, bits=bits, weight=weights[0],
                                  acc_weight=weights[1])
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(folded).view(np.uint32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("shape", [(100,), (5, 7, 11), (2048,), (1, 1), (1023,)])
def test_ops_packed_roundtrip_any_shape(bits, shape):
    """Packed payloads roundtrip odd / non-multiple-of-word sizes."""
    x = jax.random.normal(jax.random.key(3), shape) * 2
    payload = kops.quantize(jax.random.key(4), x, bits=bits, block_size=128)
    assert payload["codes"].dtype == jnp.uint32
    out = kops.dequantize(payload, bits=bits, shape=shape)
    assert out.shape == shape
    levels = 2 ** (bits - 1) - 1
    bin_w = float(np.asarray(payload["scale"]).max()) / levels
    assert float(jnp.max(jnp.abs(out - x))) <= bin_w * 1.01 + 1e-6


def test_ops_dequant_axpy_matches_unfused():
    x = jax.random.normal(jax.random.key(5), (777,))
    acc = jax.random.normal(jax.random.key(6), (777,))
    for bits in (2, 4, 8):
        p = kops.quantize(jax.random.key(7), x, bits=bits, block_size=128)
        got = kops.dequant_axpy(p, acc, bits=bits, weight=0.25)
        want = acc + 0.25 * kops.dequantize(p, bits=bits, shape=x.shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_packed_payload_measured_wire_bits():
    """bits=4, block=1024: the payload ships <= 4.1 bits/element (measured)."""
    n = 1 << 16
    p = kops.quantize(jax.random.key(0), jnp.ones((n,)), bits=4, block_size=1024)
    assert 8.0 * kops.payload_nbytes(p) / n <= 4.1
    assert (n * 4) / kops.payload_nbytes(p) >= 7.8   # >= 7.8x vs fp32


@settings(max_examples=6, deadline=None)
@given(
    rows=st.sampled_from([1, 9, 120]),        # fixed set: padded-shape reuse
    cols=st.sampled_from([128, 256]),
    bits=st.sampled_from([2, 3, 4, 5, 6, 7]),
    seed=st.integers(0, 2**31 - 1),
)
def test_packed_kernel_property_sweep(rows, cols, bits, seed):
    """Property: fused pack kernel == oracle for arbitrary row counts."""
    x = jax.random.normal(jax.random.key(seed), (rows, cols)) * 10
    s = jnp.asarray([seed], dtype=jnp.uint32)
    pk, sk = quantize_pack_2d(x, s, bits=bits, interpret=True)
    pr, sr = kref.quantize_pack_2d_ref(x, s, bits=bits)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-7)
    assert pk.shape == (rows, cols * bits // 32) and pk.dtype == jnp.uint32


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", None)])
def test_interpret_kernels_only_on_cpu(monkeypatch, backend, want):
    """Mosaic on a TPU, interpret mode on the CPU, and no silent fallback on
    any other backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            kops.interpret_kernels()
    else:
        assert kops.interpret_kernels() is want
