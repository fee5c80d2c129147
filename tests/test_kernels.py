"""Per-kernel allclose vs ref.py oracles, with hypothesis shape/dtype
sweeps (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # bare interpreter: seeded fallback shim
    from _hypothesis_compat import given, settings, strategies as st


class TestBFPMatmulKernel:
    @settings(max_examples=12, deadline=None)
    @given(
        st.integers(0, 1000),
        st.sampled_from([(8, 64, 8), (48, 100, 36), (128, 256, 128),
                         (17, 33, 9)]),
        st.sampled_from([7, 10]),
    )
    def test_vs_ref(self, seed, mkn, mb):
        from repro.kernels.bfp_matmul import bfp_matmul
        from repro.kernels.bfp_matmul.ref import bfp_matmul_ref

        M, K, N = mkn
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        a = jax.random.normal(k1, (M, K))
        b = jax.random.normal(k2, (K, N))
        got = bfp_matmul(a, b, mantissa_bits=mb, interpret=True)
        want = bfp_matmul_ref(a, b, mantissa_bits=mb)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)

    def test_dtype_bf16_inputs(self):
        from repro.kernels.bfp_matmul import bfp_matmul

        a = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(1), (64, 16), jnp.bfloat16)
        got = bfp_matmul(a, b, mantissa_bits=7, interpret=True)
        ref = a.astype(jnp.float32) @ b.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(got - ref))) / float(
            jnp.max(jnp.abs(ref))) < 0.05


def _served_activation(m, k, seed):
    """f16 activations as the data pool holds them: exponents spread
    over 30 binades, all-zero blocks, f16 subnormals, and negative
    values where the arithmetic shift floors."""
    r = np.random.default_rng(seed)
    x = r.standard_normal((m, k)) * np.exp2(r.integers(-20, 10, (m, k)))
    x[:, 32:64] = 0.0
    x[::3] = -np.abs(x[::3])
    x[1::5, :32] = 3e-6
    return jnp.asarray(x.astype(np.float16))


class TestFusedBFPMatmulKernel:
    """The one-launch 1x1 kernel: f16 activation quantized in VMEM,
    load-time BFP weights taken as they are, bias and ReLU in the
    flush."""

    # the served (K, N) classes of ResNet-50 PixelLink's 1x1 convs; M
    # spans two row tiles where K is narrow
    @pytest.mark.parametrize("m,k,n,relu", [
        (4096, 32, 9, False), (4096, 64, 32, True), (256, 288, 64, False),
        (256, 576, 256, True), (64, 1152, 2048, True),
        (64, 2048, 9, False), (128, 2048, 256, True),
    ])
    def test_vs_ref(self, m, k, n, relu):
        from repro.core import bfp
        from repro.kernels.bfp_matmul.kernel import bfp_matmul_quantized
        from repro.kernels.bfp_matmul.ref import bfp_matmul_ref

        a = _served_activation(m, k, k + n)
        r = np.random.default_rng(n)
        w = bfp.roundtrip(jnp.asarray(r.standard_normal((k, n)),
                                      jnp.float32) / np.sqrt(k), axis=0)
        b = jnp.asarray(r.standard_normal(n), jnp.float32)
        got = bfp_matmul_quantized(a, w, b, block_size=32,
                                   mantissa_bits=10, relu=relu,
                                   interpret=True)
        want = bfp_matmul_ref(a.astype(jnp.float32), w) + b
        if relu:
            want = jnp.maximum(want, 0.0)
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, atol=1e-6 * scale, rtol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float16, jnp.float32])
    @pytest.mark.parametrize("rounding", ["trunc", "nearest"])
    def test_in_kernel_quantization_is_algorithm1(self, dtype, rounding):
        """Identity weights return the quantized activation itself: each
        value equals core.bfp.quantize's mantissa * 2**(exponent - 10)
        bit for bit."""
        from repro.core import bfp
        from repro.kernels.bfp_matmul.kernel import bfp_matmul_quantized

        k = 288
        a = _served_activation(256, k, 7).astype(dtype)
        got = bfp_matmul_quantized(a, jnp.eye(k), block_size=32,
                                   mantissa_bits=10, rounding=rounding,
                                   interpret=True)
        q = bfp.quantize(a.astype(jnp.float32), block_size=32,
                         mantissa_bits=10, rounding=rounding)
        exp = np.repeat(np.asarray(q.exponent), 32, axis=1)
        want = np.ldexp(np.asarray(q.mantissa, np.float64), exp - 10)
        np.testing.assert_array_equal(np.asarray(got, np.float64), want)


class TestBFPRoundtripRows:
    """The one-pass activation round trip of the convs off the matmul
    kernel: (M, C) rows quantized and dequantized along C."""

    @pytest.mark.parametrize("m", [1, 37, 300])
    @pytest.mark.parametrize("c", [3, 20, 32, 48, 64, 256, 512])
    @pytest.mark.parametrize("dtype", [jnp.float16, jnp.float32])
    @pytest.mark.parametrize("rounding", ["trunc", "nearest"])
    def test_bit_identical_to_roundtrip(self, m, c, dtype, rounding):
        """Bit for bit ``core.bfp.roundtrip(a, axis=-1)`` on served
        activations (all-zero blocks, negatives, f16 subnormals), for C
        below one block, between blocks, dividing 128 (rows packed into
        one slab) and over several 128-lane slabs, and row counts of no
        power-of-two tile (one row; one block of 37 rows; 300 rows padded
        to a tile multiple)."""
        from repro.core import bfp
        from repro.kernels.bfp_matmul.kernel import bfp_roundtrip_rows

        a = _served_activation(m, c, c + m).astype(dtype)
        got = bfp_roundtrip_rows(a, block_size=32, mantissa_bits=10,
                                 rounding=rounding, interpret=True)
        want = bfp.roundtrip(a.astype(jnp.float32), block_size=32,
                             mantissa_bits=10, axis=-1, rounding=rounding)
        assert got.shape == (m, c) and got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                      np.asarray(want).view(np.int32))


class TestWinogradKernel:
    @settings(max_examples=10, deadline=None)
    @given(
        st.integers(0, 1000),
        st.sampled_from([(5, 7, 3, 5), (19, 23, 6, 10), (32, 32, 16, 8),
                         (12, 4, 1, 1)]),
    )
    def test_vs_direct(self, seed, hwcc):
        from repro.kernels.winograd_conv import winograd_conv2d
        from repro.kernels.winograd_conv.ref import direct_conv2d

        h, w, cin, cout = hwcc
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(k1, (2, h, w, cin))
        ker = jax.random.normal(k2, (3, 3, cin, cout))
        got = winograd_conv2d(x, ker, interpret=True)
        want = direct_conv2d(x, ker)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)

    def test_bias_fusion(self):
        from repro.kernels.winograd_conv import winograd_conv2d
        from repro.kernels.winograd_conv.ref import direct_conv2d

        x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 9, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6))
        b = jax.random.normal(jax.random.PRNGKey(2), (6,))
        got = winograd_conv2d(x, w, b, interpret=True)
        np.testing.assert_allclose(got, direct_conv2d(x, w) + b, atol=2e-3)

    def test_bias_relu_fusion(self):
        """The full in-kernel epilogue (bias + ReLU inside the output
        transform flush) against the unfused reference."""
        from repro.kernels.winograd_conv import winograd_conv2d
        from repro.kernels.winograd_conv.ref import direct_conv2d

        x = jax.random.normal(jax.random.PRNGKey(3), (2, 10, 7, 5))
        w = jax.random.normal(jax.random.PRNGKey(4), (3, 3, 5, 6))
        b = jax.random.normal(jax.random.PRNGKey(5), (6,))
        got = winograd_conv2d(x, w, b, relu=True, interpret=True)
        want = jnp.maximum(direct_conv2d(x, w) + b, 0.0)
        np.testing.assert_allclose(got, want, atol=2e-3)
        assert float(jnp.min(got)) >= 0.0

    def test_relu_without_bias(self):
        from repro.kernels.winograd_conv import winograd_conv2d
        from repro.kernels.winograd_conv.ref import direct_conv2d

        x = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 8, 4))
        w = jax.random.normal(jax.random.PRNGKey(7), (3, 3, 4, 4))
        got = winograd_conv2d(x, w, relu=True, interpret=True)
        np.testing.assert_allclose(
            got, jnp.maximum(direct_conv2d(x, w), 0.0), atol=2e-3)

    @settings(max_examples=8, deadline=None)
    @given(
        st.integers(0, 1000),
        # non-multiple-of-4 H/W so the output crop and tile padding both
        # bite; includes H or W below one 4x4 tile after VALID shrink
        st.sampled_from([(6, 9, 3, 5), (7, 7, 2, 3), (13, 5, 4, 4),
                         (5, 17, 1, 2)]),
    )
    def test_valid_padding_vs_direct(self, seed, hwcc):
        from repro.kernels.winograd_conv import winograd_conv2d
        from repro.kernels.winograd_conv.ref import direct_conv2d

        h, w, cin, cout = hwcc
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(k1, (2, h, w, cin))
        ker = jax.random.normal(k2, (3, 3, cin, cout))
        got = winograd_conv2d(x, ker, padding="VALID", interpret=True)
        want = direct_conv2d(x, ker, padding="VALID")
        assert got.shape == want.shape == (2, h - 2, w - 2, cout)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)

    def test_channels_below_block_sizes(self):
        """Cin/Cout far below the bn/bk tile sizes: the _pad_axis and
        bp_=min(bp, P) clamp paths must still produce the exact conv."""
        from repro.kernels.winograd_conv import winograd_conv2d
        from repro.kernels.winograd_conv.ref import direct_conv2d

        x = jax.random.normal(jax.random.PRNGKey(8), (1, 6, 6, 2))
        w = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 2, 3))
        got = winograd_conv2d(x, w, bp=128, bn=128, bk=128,
                              interpret=True)
        np.testing.assert_allclose(got, direct_conv2d(x, w), atol=2e-3)


class TestFlashAttentionKernel:
    @settings(max_examples=8, deadline=None)
    @given(
        st.integers(0, 1000),
        st.sampled_from([(1, 4, 4, 64, 16), (2, 8, 2, 257, 32),
                         (1, 6, 6, 100, 64), (2, 4, 1, 128, 32)]),
        st.booleans(),
    )
    def test_vs_dense(self, seed, shape, causal):
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.flash_attention.ref import mha_reference

        B, Hq, Hkv, L, D = shape
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, Hq, L, D)) * 0.3
        k = jax.random.normal(ks[1], (B, Hkv, L, D)) * 0.3
        v = jax.random.normal(ks[2], (B, Hkv, L, D))
        got = flash_attention(q, k, v, causal=causal, bq=64, bk=64,
                              interpret=True)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)

    def test_decode_attention_matches_full(self):
        from repro.kernels.flash_attention.ops import decode_attention
        from repro.kernels.flash_attention.ref import mha_reference

        B, H, K, S, D = 2, 8, 2, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, 1, D))
        kc = jax.random.normal(ks[1], (B, K, S, D))
        vc = jax.random.normal(ks[2], (B, K, S, D))
        got = decode_attention(q, kc, vc, S)
        want = mha_reference(q, kc, vc, causal=False, kv_len=S)
        np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)


class TestSSDKernel:
    @settings(max_examples=8, deadline=None)
    @given(
        st.integers(0, 1000),
        st.sampled_from([(1, 64, 2, 8, 1, 16), (2, 256, 4, 16, 2, 24),
                         (1, 128, 8, 32, 1, 64)]),
        st.sampled_from([32, 64]),
    )
    def test_vs_recurrence(self, seed, shape, chunk):
        from repro.kernels.ssd_scan import ssd_scan
        from repro.kernels.ssd_scan.ref import ssd_reference

        Bz, L, H, P, G, N = shape
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        x = jax.random.normal(ks[0], (Bz, L, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, L, H))) * 0.5
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (Bz, L, G, N)) * 0.3
        Cm = jax.random.normal(ks[4], (Bz, L, G, N)) * 0.3
        D = jax.random.normal(ks[5], (H,))
        got = ssd_scan(x, dt, A, Bm, Cm, D, chunk=min(chunk, L),
                       interpret=True)
        want = ssd_reference(x, dt, A, Bm, Cm, D)
        np.testing.assert_allclose(got, want, atol=3e-3, rtol=3e-3)

    def test_decode_step_consistency(self):
        from repro.kernels.ssd_scan.ops import ssd_decode_step
        from repro.kernels.ssd_scan.ref import ssd_reference

        Bz, L, H, P, G, N = 2, 16, 4, 8, 2, 12
        ks = jax.random.split(jax.random.PRNGKey(3), 6)
        x = jax.random.normal(ks[0], (Bz, L, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, L, H))) * 0.5
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (Bz, L, G, N)) * 0.3
        Cm = jax.random.normal(ks[4], (Bz, L, G, N)) * 0.3
        D = jax.random.normal(ks[5], (H,))
        want = ssd_reference(x, dt, A, Bm, Cm, D)
        h = jnp.zeros((Bz, H, P, N))
        for t in range(L):
            h, y = ssd_decode_step(h, x[:, t], dt[:, t], A, Bm[:, t],
                                   Cm[:, t], D)
            np.testing.assert_allclose(y, want[:, t], atol=2e-3, rtol=2e-3)
