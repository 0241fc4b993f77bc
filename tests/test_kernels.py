"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import aggregate, dequantize_unpack, quantize_pack
from repro.kernels import ref
from repro.kernels.quant_pack import dequant_unpack, quant_pack
from repro.kernels.seg_aggregate import seg_aggregate, seg_sddmm



def _heads_case(heads, dh, n=60, r=20, k=12, seed=0):
    """Features of ``heads`` column groups of ``dh``, an [r, k] layout with
    about a third of its slots padding, and per-head slot weights."""
    kx, ki, kw, km, kg = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (n, heads * dh))
    idx = jax.random.randint(ki, (r, k), 0, n)
    mask = (jax.random.uniform(km, (r, k)) > 0.3).astype(jnp.float32)
    w = jax.random.uniform(kw, (r, k, heads)) * mask[..., None]
    g = jax.random.normal(kg, (r, heads * dh))
    return x, idx, mask, w, g


class TestSegAggregateHeads:
    """The per-head form of the kernel: one row copy per slot, each head's
    lane-aligned column group weighted by its own slot weight."""

    @pytest.mark.parametrize("heads,dh", [(1, 100), (4, 32), (4, 128), (6, 47)])
    def test_matches_per_head_sums(self, heads, dh):
        x, idx, _, w, _ = _heads_case(heads, dh, seed=heads + dh)
        out = seg_aggregate(x, idx, w, interpret=True)
        xh = np.asarray(x).reshape(x.shape[0], heads, dh)
        want = np.einsum("rkh,rkhd->rhd", np.asarray(w), xh[np.asarray(idx)])
        np.testing.assert_allclose(out, want.reshape(out.shape), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out, ref.seg_aggregate_ref(x, idx, w),
                                   rtol=1e-5, atol=1e-5)

    def test_one_head_is_the_scalar_weight_kernel_bit_for_bit(self):
        x, idx, mask, w, _ = _heads_case(1, 256, seed=3)
        scalar = seg_aggregate(x, idx, w[..., 0], interpret=True)
        headed = seg_aggregate(x, idx, w, interpret=True)
        assert np.array_equal(np.asarray(headed), np.asarray(scalar))

    def test_zero_weight_heads_add_exact_zeros(self):
        """A slot whose weight is 0 in one head and not another is fetched
        once; the zero head adds nothing, whatever the row holds."""
        x, idx, mask, w, _ = _heads_case(4, 32, seed=5)
        w = w.at[:, :, 2].set(0.0)
        x = x.at[idx[0, 0]].set(jnp.inf)
        w = w.at[0, 0].set(jnp.array([0.5, 0.25, 0.0, 1.0]))
        out = np.asarray(seg_aggregate(x, idx, w, interpret=True))
        assert np.all(out[:, 64:96] == 0.0)

    @pytest.mark.parametrize("heads,dh", [(1, 100), (4, 32), (6, 47)])
    def test_sddmm_is_the_per_slot_dot_products(self, heads, dh):
        x, idx, mask, _, g = _heads_case(heads, dh, seed=7 + heads)
        out = seg_sddmm(g, x, idx, mask, heads=heads, interpret=True)
        xh = np.asarray(x).reshape(x.shape[0], heads, dh)[np.asarray(idx)]
        gh = np.asarray(g).reshape(g.shape[0], heads, dh)
        want = np.einsum("rhd,rkhd->rkh", gh, xh) * np.asarray(mask)[..., None]
        assert out.shape == (idx.shape[0], idx.shape[1], heads)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(out, ref.seg_sddmm_ref(g, x, idx, mask, heads),
                                   rtol=1e-5, atol=1e-4)


class TestSegAggregate:
    @pytest.mark.parametrize("n,f,r,k", [
        (64, 128, 8, 1),
        (300, 256, 64, 20),
        (1000, 384, 256, 33),
        (128, 128, 16, 7),
        (50, 512, 8, 5),
    ])
    def test_matches_oracle_shapes(self, n, f, r, k):
        kx, ki, kw, km = jax.random.split(jax.random.PRNGKey(n + f + r + k), 4)
        x = jax.random.normal(kx, (n, f))
        idx = jax.random.randint(ki, (r, k), 0, n)
        w = jax.random.uniform(kw, (r, k)) * (jax.random.uniform(km, (r, k)) > 0.3)
        out = seg_aggregate(x, idx, w, interpret=True)
        expect = ref.seg_aggregate_ref(x, idx, w)
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        kx, ki = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (100, 128)).astype(dtype)
        idx = jax.random.randint(ki, (16, 9), 0, 100)
        w = jnp.ones((16, 9), jnp.float32)
        out = seg_aggregate(x, idx, w, interpret=True)
        expect = ref.seg_aggregate_ref(x, idx, w)
        assert out.dtype == dtype
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   expect.astype(jnp.float32),
                                   rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                                   atol=1e-1 if dtype == jnp.bfloat16 else 1e-5)

    def test_block_shape_sweep(self):
        """Different BlockSpec tilings must not change the result."""
        kx, ki, kw = jax.random.split(jax.random.PRNGKey(3), 3)
        x = jax.random.normal(kx, (200, 256))
        idx = jax.random.randint(ki, (32, 12), 0, 200)
        w = jax.random.uniform(kw, (32, 12))
        expect = ref.seg_aggregate_ref(x, idx, w)
        # block_k sets the slot chunk per grid step (and with it the row
        # tile, 1024 / block_k): 1 and 4 chunk K=12 into many steps, 16 and
        # 64 pad it into one.
        for bk in (1, 4, 16, 64):
            out = seg_aggregate(x, idx, w, block_k=bk, interpret=True)
            np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5,
                                       err_msg=f"block_k={bk}")

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 8), st.integers(1, 24), st.integers(0, 9999))
    def test_linearity_property(self, rows8, k, seed):
        """Aggregation is linear: agg(a*x) == a*agg(x)."""
        r = rows8 * 8
        kx, ki, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = jax.random.normal(kx, (64, 128))
        idx = jax.random.randint(ki, (r, k), 0, 64)
        w = jax.random.uniform(kw, (r, k))
        out1 = seg_aggregate(x, idx, w, interpret=True)
        out2 = seg_aggregate(2.5 * x, idx, w, interpret=True)
        np.testing.assert_allclose(2.5 * out1, out2, rtol=1e-4, atol=1e-4)

    @staticmethod
    def _padded_layout(seed, n, r, k, max_deg, min_src=0, by_degree=False):
        """Rows of degree 1..max_deg (row r has degree 1 + r % max_deg, or
        those degrees highest first, as the bucketed layout orders them),
        padded to K with id 0 and weight 0, as the bucketed layout pads."""
        rng = np.random.default_rng(seed)
        deg = 1 + np.arange(r) % max_deg
        if by_degree:
            deg = np.sort(deg)[::-1]
        real = np.arange(k)[None, :] < deg[:, None]
        idx = np.where(real, rng.integers(min_src, n, (r, k)), 0)
        w = np.where(real, rng.uniform(0.1, 1.0, (r, k)), 0.0)
        return idx.astype(np.int32), w.astype(np.float32)

    @staticmethod
    def _slot_order_sum(x, idx, w):
        """float32 sum of w[r, k] * x[idx[r, k]] for k = 0..K-1, in order."""
        acc = np.zeros((idx.shape[0], x.shape[1]), np.float32)
        for k in range(idx.shape[1]):
            acc = acc + w[:, k:k + 1] * x[idx[:, k]]
        return acc

    @pytest.mark.parametrize("n,f,r,k,max_deg,by_degree", [
        (300, 256, 64, 8, 8, False),     # mixed degree inside one bucket
        (300, 256, 200, 8, 8, True),     # the same, highest degree first
        (40, 60, 5, 3, 3, False),        # rows round up to BR, K to KC, F to lanes
        (200, 128, 48, 20, 16, False),   # the second K chunk is all padding
        (150, 128, 128, 8, None, False),  # no zero weight: nothing is skipped
    ])
    def test_padding_slots_match_slot_order_sum(self, n, f, r, k, max_deg,
                                                by_degree):
        """Skipping zero-weight slots leaves the kernel's sum what it was: the
        reference within 1e-5, and the float32 sum in slot order within one
        ulp a slot. Not bit for bit: the interpreter runs the kernel through
        XLA's CPU backend, which fuses some multiplies into their adds, so
        the kernel that fetched every slot missed the plain sum too."""
        rng = np.random.default_rng(n + r + k)
        x = rng.standard_normal((n, f)).astype(np.float32)
        if max_deg is None:
            idx = rng.integers(0, n, (r, k)).astype(np.int32)
            w = rng.uniform(0.1, 1.0, (r, k)).astype(np.float32)
        else:
            idx, w = self._padded_layout(r, n, r, k, max_deg,
                                         by_degree=by_degree)
        out = np.asarray(seg_aggregate(x, idx, w, interpret=True))
        np.testing.assert_allclose(out, ref.seg_aggregate_ref(x, idx, w),
                                   rtol=1e-5, atol=1e-5)
        # Each slot rounds its product or its fused sum at most once, below
        # one ulp of the sum of |w x|, which bounds every partial sum.
        ulp = np.spacing(self._slot_order_sum(np.abs(x), idx, np.abs(w)))
        assert (np.abs(out - self._slot_order_sum(x, idx, w)) <= k * ulp).all()

    @pytest.mark.parametrize("by_degree", [False, True])
    def test_zero_weight_rows_never_reach_the_sum(self, by_degree):
        """NaN in row 0 (the padding id) and Inf in a row reached only through
        zero weights leave the output finite and bit-equal to the output with
        those rows zeroed: no zero-weight slot's source row reaches the sum
        (a kernel that added it would add 0 * inf = NaN), whether the slot
        trails its column (not fetched) or not (fetched, then dropped)."""
        n, f, r, k = 64, 128, 32, 8
        rng = np.random.default_rng(7)
        x = rng.standard_normal((n, f)).astype(np.float32)
        idx, w = self._padded_layout(1, n, r, k, 5, min_src=2,
                                     by_degree=by_degree)
        pad = w == 0
        idx[pad] = np.broadcast_to(np.arange(k) % 2, (r, k))[pad]
        assert (idx[pad] == 0).any() and (idx[pad] == 1).any()
        clean = x.copy()
        clean[:2] = 0.0
        bad = clean.copy()
        bad[0], bad[1] = np.nan, np.inf
        out = np.asarray(seg_aggregate(bad, idx, w, interpret=True))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(
            out, np.asarray(seg_aggregate(clean, idx, w, interpret=True)))

    def test_unaligned_falls_back(self):
        """Unaligned widths and row counts stay on the kernel: the wrapper
        pads them (60 -> 128 lanes, 5 -> one row tile) and slices back."""
        x = jnp.ones((10, 60))       # 60 not a lane multiple
        idx = jnp.zeros((5, 3), jnp.int32)
        w = jnp.ones((5, 3))
        out = aggregate(x, idx, w)
        np.testing.assert_allclose(out, 3.0 * jnp.ones((5, 60)))


class TestQuantPack:
    @pytest.mark.parametrize("bits", [2, 4, 8])
    @pytest.mark.parametrize("rows,feat", [(8, 32), (128, 256), (64, 48)])
    def test_matches_oracle(self, bits, rows, feat):
        per_word = 32 // bits
        if feat % per_word:
            pytest.skip("unaligned feat")
        kx, kn = jax.random.split(jax.random.PRNGKey(bits * rows + feat))
        x = jax.random.normal(kx, (rows, feat)) * 3 + 1
        noise = jax.random.uniform(kn, (rows, feat))
        pk, zk, sk = quant_pack(x, noise, bits=bits, interpret=True)
        pr, zr, sr = ref.quant_pack_ref(x, noise, bits)
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
        np.testing.assert_allclose(zk, zr, rtol=1e-6)
        np.testing.assert_allclose(sk, sr, rtol=1e-6)
        dk = dequant_unpack(pk, zk, sk, bits=bits, feat=feat, interpret=True)
        dr = ref.dequant_unpack_ref(pr, zr, sr, bits, feat)
        np.testing.assert_allclose(dk, dr, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bits", [2, 4, 8])
    def test_roundtrip_error_bound(self, bits):
        """|dequant(quant(x)) - x| <= one quantization step per row group."""
        kx, kn = jax.random.split(jax.random.PRNGKey(7))
        x = jax.random.normal(kx, (64, 64)) * 5
        noise = jax.random.uniform(kn, (64, 64))
        pk, z, s = quantize_pack(x, noise, bits=bits)
        xd = dequantize_unpack(pk, z, s, bits=bits, feat=64)
        err = jnp.abs(xd - x).reshape(16, -1).max(axis=1)
        np.testing.assert_array_less(np.asarray(err), np.asarray(s) * 1.001 + 1e-6)

    def test_stochastic_rounding_unbiased(self):
        x = jnp.full((4, 64), 0.37) + jnp.linspace(0, 1, 64)
        acc = jnp.zeros_like(x)
        n = 300
        for i in range(n):
            kn = jax.random.PRNGKey(i)
            noise = jax.random.uniform(kn, x.shape)
            pk, z, s = quantize_pack(x, noise, bits=2)
            acc = acc + dequantize_unpack(pk, z, s, bits=2, feat=64)
        bias = float(jnp.abs(acc / n - x).max())
        assert bias < 0.08, bias  # E[dequant] -> x

    def test_constant_rows(self):
        """Degenerate range (max == min) must not produce NaNs."""
        x = jnp.full((8, 32), 3.14)
        noise = jnp.full((8, 32), 0.5)
        pk, z, s = quantize_pack(x, noise, bits=2)
        xd = dequantize_unpack(pk, z, s, bits=2, feat=32)
        assert jnp.isfinite(xd).all()
        np.testing.assert_allclose(xd, x, rtol=1e-6)

    @settings(max_examples=10, deadline=None)
    @given(st.sampled_from([2, 4, 8]), st.integers(1, 16), st.integers(0, 9999))
    def test_pack_is_lossless_property(self, bits, groups, seed):
        """pack -> unpack is exact for any quantized payload."""
        from repro.quant.stochastic import pack_bits, unpack_bits
        rows = groups * 4
        levels = (1 << bits) - 1
        q = jax.random.randint(jax.random.PRNGKey(seed), (rows, 32), 0, levels + 1)
        packed = pack_bits(q, bits)
        q2 = unpack_bits(packed, bits, 32)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
