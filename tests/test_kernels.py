"""Pallas kernel tests: interpret-mode kernel body vs pure-jnp oracle,
swept over shapes, dtypes and codebooks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributions as dist
from repro.core import element as el
from repro.core.nibble import nibble_k_tile, pack_nibbles
from repro.kernels.block_quant.block_quant import block_quant as bq_pallas
from repro.kernels.block_quant.ref import block_quant_ref, block_dequant_ref
from repro.kernels.dequant_matmul import tune
from repro.kernels.dequant_matmul.dequant_matmul import \
    dequant_matmul as dqm_pallas
from repro.kernels.dequant_matmul.dequant_matmul import \
    dequant_matmul_t as dqmt_pallas
from repro.kernels.dequant_matmul.ref import (dequant_matmul_decode_ref,
                                              dequant_matmul_ref,
                                              dequant_matmul_t_decode_ref,
                                              dequant_matmul_t_ref)

CODEBOOKS = {
    "int4": el.int_format(4).np_codepoints(),
    "t4_absmax": el.cube_root_absmax(dist.StudentT(nu=7), 4, 128)
    .np_codepoints(),
    "nf4": el.nf4().np_codepoints(),
    "int8": el.int_format(8).np_codepoints(),
}


def rand(shape, dtype=jnp.float32, seed=0, scale=1.0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.standard_normal(shape) * scale, dtype)


class TestBlockQuantKernel:
    @pytest.mark.parametrize("cb_name", list(CODEBOOKS))
    @pytest.mark.parametrize("shape", [(256, 512), (512, 1024)])
    def test_matches_oracle(self, cb_name, shape):
        cb = jnp.asarray(CODEBOOKS[cb_name], jnp.float32)
        x = rand(shape, seed=hash((cb_name, shape)) % 2**31)
        codes_k, scales_k = bq_pallas(x, cb, interpret=True)
        codes_r, scales_r = block_quant_ref(x, cb)
        np.testing.assert_allclose(np.asarray(scales_k), np.asarray(scales_r))
        # codes may differ at exact midpoints (fp associativity): compare
        # dequantised values instead of raw codes
        dk = block_dequant_ref(codes_k, scales_k, cb)
        dr = block_dequant_ref(codes_r, scales_r, cb)
        np.testing.assert_allclose(np.asarray(dk, np.float32),
                                   np.asarray(dr, np.float32),
                                   rtol=1e-2, atol=1e-2)
        mismatch = (np.asarray(codes_k) != np.asarray(codes_r)).mean()
        assert mismatch < 1e-3

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((256, 512), dtype)
        codes, scales = bq_pallas(x, cb, interpret=True)
        assert codes.dtype == jnp.uint8 and scales.dtype == jnp.float32
        # round trip error bounded by half the max codepoint gap × scale
        y = block_dequant_ref(codes, scales, cb)
        err = np.abs(np.asarray(y, np.float32) - np.asarray(x, np.float32))
        bound = np.asarray(scales).repeat(128, -1).reshape(err.shape)
        half_gap = float(np.diff(np.asarray(cb)).max()) / 2
        assert (err <= bound * half_gap * 1.05 + 1e-3).all()

    def test_scale_round_away_property(self):
        """Normalised data never exceeds ±1 after bf16 scale quantisation."""
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((256, 512), seed=3, scale=7.3)
        codes, scales = bq_pallas(x, cb, interpret=True)
        xb = np.asarray(x).reshape(256, 4, 128)
        assert (np.abs(xb) <= np.asarray(scales)[..., None] + 1e-6).all()


class TestDequantMatmulKernel:
    @pytest.mark.parametrize("cb_name", ["int4", "t4_absmax", "int8"])
    @pytest.mark.parametrize("mkn", [(128, 256, 256), (256, 512, 512)])
    def test_matches_oracle(self, cb_name, mkn):
        M, K, N = mkn
        cb = jnp.asarray(CODEBOOKS[cb_name], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=1)
        w = rand((K, N), seed=2, scale=0.1)
        codes, scales = block_quant_ref(w, cb)
        y_k = dqm_pallas(x, codes, scales, cb, interpret=True)
        y_r = dequant_matmul_ref(x, codes, scales, cb)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_end_to_end_vs_bf16_matmul(self):
        """Quantise→fused-matmul ≈ the bf16 matmul (int8: tight match)."""
        M, K, N = 128, 256, 256
        cb = jnp.asarray(CODEBOOKS["int8"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=4)
        w = rand((K, N), seed=5, scale=0.05)
        codes, scales = block_quant_ref(w, cb)
        y_q = dqm_pallas(x, codes, scales, cb, interpret=True)
        y_f = jnp.dot(x.astype(jnp.float32), np.asarray(w)).astype(jnp.bfloat16)
        rel = (np.linalg.norm(np.asarray(y_q, np.float32) -
                              np.asarray(y_f, np.float32)) /
               np.linalg.norm(np.asarray(y_f, np.float32)))
        assert rel < 0.02

    def test_grid_accumulation_over_k(self):
        """K spans multiple tiles: accumulation must be exact."""
        M, K, N = 128, 1024, 256  # K/TILE_K = 4 steps
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=6)
        w = rand((K, N), seed=7, scale=0.1)
        codes, scales = block_quant_ref(w, cb)
        y_k = dqm_pallas(x, codes, scales, cb, interpret=True)
        y_r = dequant_matmul_ref(x, codes, scales, cb)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)


class TestNibblePackedKernel:
    """bits=4: the kernel reads (TK/2, TN) byte tiles from HBM and unpacks
    nibbles in VMEM; the oracle unpack is bit-exact, so packed and unpacked
    storage must agree exactly, and kernel-vs-oracle to MXU tolerance."""

    @pytest.mark.parametrize("cb_name", ["int4", "t4_absmax", "nf4"])
    @pytest.mark.parametrize("mkn", [(128, 256, 256), (128, 512, 256)])
    def test_matches_oracle(self, cb_name, mkn):
        M, K, N = mkn
        cb = jnp.asarray(CODEBOOKS[cb_name], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=hash((cb_name, mkn)) % 2**31)
        w = rand((K, N), seed=11, scale=0.1)
        codes, scales = block_quant_ref(w, cb)
        packed = pack_nibbles(codes)
        assert packed.shape == (K // 2, N)
        y_k = dqm_pallas(x, packed, scales, cb, bits=4, interpret=True)
        y_r = dequant_matmul_ref(x, packed, scales, cb, bits=4)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_oracle_bit_identical_to_unpacked(self):
        """Nibble unpack restores the exact codes: the bits=4 oracle equals
        the bits=8 oracle bit for bit (K spans multiple interleave tiles)."""
        M, K, N = 64, 512, 256
        cb = jnp.asarray(CODEBOOKS["t4_absmax"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=12)
        codes, scales = block_quant_ref(rand((K, N), seed=13, scale=0.1), cb)
        y4 = dequant_matmul_ref(x, pack_nibbles(codes), scales, cb, bits=4)
        y8 = dequant_matmul_ref(x, codes, scales, cb, bits=8)
        np.testing.assert_array_equal(np.asarray(y4, np.float32),
                                      np.asarray(y8, np.float32))

    def test_kernel_packed_matches_kernel_unpacked(self):
        """Same codes through both storage widths of the Pallas body."""
        M, K, N = 128, 256, 256
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=14)
        codes, scales = block_quant_ref(rand((K, N), seed=15, scale=0.1), cb)
        y4 = dqm_pallas(x, pack_nibbles(codes), scales, cb, bits=4,
                        interpret=True)
        y8 = dqm_pallas(x, codes, scales, cb, bits=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(y4, np.float32),
                                      np.asarray(y8, np.float32))

    def test_leading_expert_dim_matches_per_expert(self):
        """The batched lead dim (MoE expert stacks) equals per-expert 2-D
        calls, packed and unpacked."""
        E, M, K, N = 3, 64, 256, 128
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((E, M, K), jnp.bfloat16, seed=16)
        pairs = [block_quant_ref(rand((K, N), seed=20 + e, scale=0.1), cb)
                 for e in range(E)]
        codes = jnp.stack([c for c, _ in pairs])
        scales = jnp.stack([s for _, s in pairs])
        packed = pack_nibbles(codes)
        y_b = dqm_pallas(x, packed, scales, cb, bits=4, interpret=True)
        assert y_b.shape == (E, M, N)
        for e in range(E):
            y_e = dqm_pallas(x[e], packed[e], scales[e], cb, bits=4,
                             interpret=True)
            np.testing.assert_array_equal(np.asarray(y_b[e]), np.asarray(y_e))
        y_r = dequant_matmul_ref(x, packed, scales, cb, bits=4)
        np.testing.assert_allclose(np.asarray(y_b, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)


class TestTransposedDequantMatmul:
    """The transposed variant (tied-embeddings unembed): y = x @ W.T with W
    stored codes (V, D) + scales blocked along D — the contraction runs
    along the blocked axis, and with bits=4 the nibble interleave runs
    along the *output* (V) axis."""

    @pytest.mark.parametrize("cb_name", ["int4", "t4_absmax", "int8"])
    @pytest.mark.parametrize("mdv", [(128, 256, 256), (128, 256, 512)])
    def test_matches_oracle_uint8(self, cb_name, mdv):
        M, D, V = mdv
        cb = jnp.asarray(CODEBOOKS[cb_name], jnp.float32)
        x = rand((M, D), jnp.bfloat16, seed=hash((cb_name, mdv)) % 2**31)
        w = rand((V, D), seed=31, scale=0.1)
        codes, scales = block_quant_ref(w, cb)
        y_k = dqmt_pallas(x, codes, scales, cb, interpret=True)
        y_r = dequant_matmul_t_ref(x, codes, scales, cb)
        assert y_k.shape == (M, V)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    @pytest.mark.parametrize("cb_name", ["int4", "nf4"])
    def test_matches_oracle_nibble(self, cb_name):
        M, D, V = 128, 256, 512
        cb = jnp.asarray(CODEBOOKS[cb_name], jnp.float32)
        x = rand((M, D), jnp.bfloat16, seed=32)
        codes, scales = block_quant_ref(rand((V, D), seed=33, scale=0.1), cb)
        packed = pack_nibbles(codes)       # nibble interleave along V
        assert packed.shape == (V // 2, D)
        y_k = dqmt_pallas(x, packed, scales, cb, bits=4, interpret=True)
        y_r = dequant_matmul_t_ref(x, packed, scales, cb, bits=4)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_nibble_bit_identical_to_uint8(self):
        """Both the oracle and the kernel body must be bit-identical across
        the two storage widths (unpack restores the exact codes)."""
        M, D, V = 128, 256, 512
        cb = jnp.asarray(CODEBOOKS["t4_absmax"], jnp.float32)
        x = rand((M, D), jnp.bfloat16, seed=34)
        codes, scales = block_quant_ref(rand((V, D), seed=35, scale=0.1), cb)
        packed = pack_nibbles(codes)
        np.testing.assert_array_equal(
            np.asarray(dequant_matmul_t_ref(x, packed, scales, cb, bits=4),
                       np.float32),
            np.asarray(dequant_matmul_t_ref(x, codes, scales, cb, bits=8),
                       np.float32))
        np.testing.assert_array_equal(
            np.asarray(dqmt_pallas(x, packed, scales, cb, bits=4,
                                   interpret=True)),
            np.asarray(dqmt_pallas(x, codes, scales, cb, bits=8,
                                   interpret=True)))

    def test_grid_accumulation_over_blocked_axis(self):
        """D spans multiple tiles: accumulation along the blocked
        contraction axis must be exact."""
        M, D, V = 128, 1024, 256   # D spans several tiles
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, D), jnp.bfloat16, seed=36)
        codes, scales = block_quant_ref(rand((V, D), seed=37, scale=0.1), cb)
        y_k = dqmt_pallas(x, pack_nibbles(codes), scales, cb, bits=4,
                          interpret=True)
        y_r = dequant_matmul_t_ref(x, pack_nibbles(codes), scales, cb, bits=4)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_transposed_oracle_equals_plain_matmul_of_transpose(self):
        """dequant_matmul_t_ref(x, W) == x @ dequantise(W).T elementwise."""
        M, D, V = 64, 256, 256
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, D), jnp.float32, seed=38)
        codes, scales = block_quant_ref(rand((V, D), seed=39, scale=0.1), cb)
        w = np.asarray(cb)[np.asarray(codes).astype(np.int32)].reshape(
            V, -1, 128) * np.asarray(scales, np.float32)[..., None]
        ref = np.asarray(x, np.float32) @ w.reshape(V, D).T
        got = dequant_matmul_t_ref(x, codes, scales, cb)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=2e-5, atol=2e-5)


class TestDecodeVariantKernel:
    """The small-M decode strategy (``variant="decode"``): direct
    select-tree/gather dequant on the VPU with the block scale folded into
    the accumulation, instead of the one-hot LUT matmul."""

    @pytest.mark.parametrize("cb_name", ["int4", "t4_absmax", "int8"])
    @pytest.mark.parametrize("M", [1, 8])
    def test_matches_oracle(self, cb_name, M):
        K, N = 256, 256
        cb = jnp.asarray(CODEBOOKS[cb_name], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=hash((cb_name, M)) % 2**31)
        codes, scales = block_quant_ref(rand((K, N), seed=41, scale=0.1), cb)
        y_k = dqm_pallas(x, codes, scales, cb, interpret=True,
                         variant="decode")
        y_r = dequant_matmul_ref(x, codes, scales, cb)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_grid_accumulation_over_k(self):
        """K spans multiple tiles under the decode body too."""
        M, K, N = 8, 1024, 256
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=42)
        codes, scales = block_quant_ref(rand((K, N), seed=43, scale=0.1), cb)
        y_k = dqm_pallas(x, pack_nibbles(codes), scales, cb, bits=4,
                         interpret=True, variant="decode")
        y_r = dequant_matmul_ref(x, pack_nibbles(codes), scales, cb, bits=4)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_bit_identical_across_storage(self):
        """Decode body over nibble-packed vs uint8 codes: exact agreement
        (the unpack restores the exact codes; the select tree then sees
        identical inputs). K spans multiple interleave tiles."""
        M, K, N = 8, 512, 256
        cb = jnp.asarray(CODEBOOKS["t4_absmax"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=44)
        codes, scales = block_quant_ref(rand((K, N), seed=45, scale=0.1), cb)
        y4 = dqm_pallas(x, pack_nibbles(codes), scales, cb, bits=4,
                        interpret=True, variant="decode")
        y8 = dqm_pallas(x, codes, scales, cb, bits=8, interpret=True,
                        variant="decode")
        np.testing.assert_array_equal(np.asarray(y4, np.float32),
                                      np.asarray(y8, np.float32))

    @pytest.mark.parametrize("cb_name", ["int4", "int8"])
    def test_transposed_decode_variant(self, cb_name):
        """Transposed decode body (scale folded into the output tile)."""
        M, D, V = 3, 256, 512
        cb = jnp.asarray(CODEBOOKS[cb_name], jnp.float32)
        x = rand((M, D), jnp.bfloat16, seed=46)
        codes, scales = block_quant_ref(rand((V, D), seed=47, scale=0.1), cb)
        y_k = dqmt_pallas(x, codes, scales, cb, interpret=True,
                          variant="decode")
        y_r = dequant_matmul_t_ref(x, codes, scales, cb)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_transposed_bit_identical_across_storage(self):
        M, D, V = 3, 256, 512
        cb = jnp.asarray(CODEBOOKS["nf4"], jnp.float32)
        x = rand((M, D), jnp.bfloat16, seed=48)
        codes, scales = block_quant_ref(rand((V, D), seed=49, scale=0.1), cb)
        y4 = dqmt_pallas(x, pack_nibbles(codes), scales, cb, bits=4,
                         interpret=True, variant="decode")
        y8 = dqmt_pallas(x, codes, scales, cb, bits=8, interpret=True,
                         variant="decode")
        np.testing.assert_array_equal(np.asarray(y4, np.float32),
                                      np.asarray(y8, np.float32))

    def test_variants_agree(self):
        """Both strategies compute the same matmul (LUT to bf16-feed
        tolerance): forcing either variant never changes semantics."""
        M, K, N = 8, 512, 256
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=50)
        codes, scales = block_quant_ref(rand((K, N), seed=51, scale=0.1), cb)
        y_d = dqm_pallas(x, codes, scales, cb, interpret=True,
                         variant="decode")
        y_l = dqm_pallas(x, codes, scales, cb, interpret=True, variant="lut")
        np.testing.assert_allclose(np.asarray(y_d, np.float32),
                                   np.asarray(y_l, np.float32),
                                   rtol=2e-2, atol=2e-1)


class TestNonMultipleM:
    """Regression: M need not divide the M tile — the wrappers pad with
    zero rows and slice the output (e.g. a B·prefill_chunk = 192 chunk
    used to trip ``assert M % tm == 0``)."""

    @pytest.mark.parametrize("M", [5, 192])
    def test_normal_pads_m(self, M):
        K, N = 256, 256
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, K), jnp.bfloat16, seed=52)
        codes, scales = block_quant_ref(rand((K, N), seed=53, scale=0.1), cb)
        y_k = dqm_pallas(x, pack_nibbles(codes), scales, cb, bits=4,
                         interpret=True)
        assert y_k.shape == (M, N)
        y_r = dequant_matmul_ref(x, pack_nibbles(codes), scales, cb, bits=4)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    @pytest.mark.parametrize("M", [5, 192])
    def test_transposed_pads_m(self, M):
        D, V = 256, 512
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, D), jnp.bfloat16, seed=54)
        codes, scales = block_quant_ref(rand((V, D), seed=55, scale=0.1), cb)
        y_k = dqmt_pallas(x, codes, scales, cb, interpret=True)
        assert y_k.shape == (M, V)
        y_r = dequant_matmul_t_ref(x, codes, scales, cb)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)

    def test_lead_dim_pads_m(self):
        """MoE dispatch capacity not a tile multiple, batched lead dim."""
        E, C, K, N = 2, 20, 256, 128
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((E, C, K), jnp.bfloat16, seed=56)
        pairs = [block_quant_ref(rand((K, N), seed=60 + e, scale=0.1), cb)
                 for e in range(E)]
        codes = jnp.stack([c for c, _ in pairs])
        scales = jnp.stack([s for _, s in pairs])
        y_k = dqm_pallas(x, codes, scales, cb, interpret=True)
        assert y_k.shape == (E, C, N)
        y_r = dequant_matmul_ref(x, codes, scales, cb)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=2e-2, atol=2e-1)


class TestTuningTable:
    def test_strategy_crossover(self):
        """Decode strategy at serving M, LUT at prefill/training M."""
        for M in (1, 4, 8):
            assert tune.choose_tiles(M, 768, 2048, 4).decode, M
        assert not tune.choose_tiles(4096, 768, 2048, 4).decode

    def test_tiles_legal(self):
        for (M, K, N, bits) in [(1, 768, 32768, 4), (192, 2048, 768, 4),
                                (8, 512, 512, 8), (256, 768, 256, 8)]:
            c = tune.choose_tiles(M, K, N, bits)
            assert K % c.tk == 0 and N % c.tn == 0
            assert c.tn % tune.BLOCK == 0
            if bits == 4:
                # layout-locked to the nibble interleave tile
                assert c.tk == nibble_k_tile(K)

    def test_register_overrides(self):
        """A measured-sweep override wins over the analytic choice."""
        key = (7, 256, 256, 8)       # geometry unlikely to matter elsewhere
        forced = tune.TileChoice(8, 256, 128, False)
        tune.register(*key, forced)
        assert tune.choose_tiles(*key) == forced


class TestDecodeRefs:
    """The decode-shaped jnp oracles the CPU serving fallback dispatches
    to: bit-identical to the plain refs for M ≥ 2 (full-K dots; panels
    split only the output axis); M == 1 is padded for speed and agrees to
    reassociation tolerance."""

    @pytest.mark.parametrize("bits", [4, 8])
    @pytest.mark.parametrize("M", [2, 4, 8])
    def test_bit_identical_with_panels(self, bits, M):
        K, N = 768, 8192             # narrow K ⇒ the N-panel path is live
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, K), seed=61)
        codes, scales = block_quant_ref(rand((K, N), seed=62, scale=0.1), cb)
        c = pack_nibbles(codes) if bits == 4 else codes
        np.testing.assert_array_equal(
            np.asarray(dequant_matmul_decode_ref(x, c, scales, cb,
                                                 bits=bits), np.float32),
            np.asarray(dequant_matmul_ref(x, c, scales, cb, bits=bits),
                       np.float32))

    @pytest.mark.parametrize("bits", [4, 8])
    def test_m1_padded_close(self, bits):
        K, N = 768, 8192
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((1, K), seed=63)
        codes, scales = block_quant_ref(rand((K, N), seed=64, scale=0.1), cb)
        c = pack_nibbles(codes) if bits == 4 else codes
        np.testing.assert_allclose(
            np.asarray(dequant_matmul_decode_ref(x, c, scales, cb,
                                                 bits=bits), np.float32),
            np.asarray(dequant_matmul_ref(x, c, scales, cb, bits=bits),
                       np.float32), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("bits", [4, 8])
    @pytest.mark.parametrize("M", [2, 4])
    def test_transposed_bit_identical(self, bits, M):
        V, D = 2048, 768             # M=4 panels along V; M=2 single piece
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((M, D), seed=65)
        codes, scales = block_quant_ref(rand((V, D), seed=66, scale=0.1), cb)
        c = pack_nibbles(codes) if bits == 4 else codes
        np.testing.assert_array_equal(
            np.asarray(dequant_matmul_t_decode_ref(x, c, scales, cb,
                                                   bits=bits), np.float32),
            np.asarray(dequant_matmul_t_ref(x, c, scales, cb, bits=bits),
                       np.float32))

    def test_ops_dispatches_decode_shapes(self):
        """The CPU fallback routes every 2-D call (decode rows and prefill
        chunks alike) through the decode oracle — same values as the plain
        oracle at M ≥ 2."""
        from repro.kernels import ops
        K, N = 256, 512
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        codes, scales = block_quant_ref(rand((K, N), seed=67, scale=0.1), cb)
        for M in (2, 8, 32):
            x = rand((M, K), seed=68)
            np.testing.assert_array_equal(
                np.asarray(ops.dequant_matmul(x, codes, scales, cb),
                           np.float32),
                np.asarray(dequant_matmul_ref(x, codes, scales, cb),
                           np.float32))


class TestOpsWrapper:
    def test_fallback_on_cpu(self):
        from repro.kernels import ops
        cb = jnp.asarray(CODEBOOKS["int4"], jnp.float32)
        x = rand((256, 512))
        codes, scales = ops.block_quant(x, cb)
        y = ops.block_dequant(codes, scales, cb)
        assert y.shape == x.shape
