"""QuantisationPlan pack/unpack: the serving representation (PackedTensor,
matmul-layout codes + block scales, nibble-packed for ≤16-point codebooks)
must round-trip exactly against the storage representation
(QuantisedTensor) and TensorFormat's own quantise→dequantise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import PackedTensor, QuantisedTensor, build_plan, parse_format
from repro.core.nibble import (nibble_k_tile, nibble_row_coords, pack_nibbles,
                               unpack_nibbles)
from repro.core.plan import QuantisationPlan, path_str


def _params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "embed": jnp.asarray(rng.standard_normal((128, 64)), jnp.float32),
        "layers": {
            "wq": jnp.asarray(rng.standard_normal((2, 64, 2, 32)),
                              jnp.float32),
            "wo": jnp.asarray(rng.standard_normal((2, 2, 32, 64)),
                              jnp.float32),
            "norm": jnp.ones((2, 64), jnp.float32),  # not quantisable
        },
        "unembed": jnp.asarray(rng.standard_normal((64, 128)), jnp.float32),
    }


LAYOUTS = {
    "['embed']": (0, 1),
    "['layers']['wq']": (1, 1),
    "['layers']['wo']": (1, 2),
    "['unembed']": (0, 1),
}


class TestPackQuantised:
    def setup_method(self, _):
        self.params = _params()
        self.plan = build_plan(self.params, "babsmax32:n4")
        assert self.plan.formats["['layers']['norm']"] is None
        self.q = self.plan.quantise(self.params)
        self.packed = self.plan.pack_quantised(self.q, LAYOUTS)

    def test_dtypes_and_shapes(self):
        pk = self.packed
        wq = pk["layers"]["wq"]
        assert isinstance(wq, PackedTensor)
        assert wq.codes.dtype == jnp.uint8
        assert wq.scales.dtype == jnp.bfloat16
        # n4 = 16 codepoints → nibble-packed: two codes/byte along K
        assert wq.bits == 4 and wq.k_dim == 64
        assert wq.codes.shape == (2, 32, 64)        # (L, K//2=D/2, N=H*hd)
        assert wq.scales.shape == (2, 64, 2)        # N // block = 64/32
        assert wq.out_shape == (2, 32)
        wo = pk["layers"]["wo"]
        assert wo.codes.shape == (2, 32, 64)        # (L, K//2=H*hd/2, N=D)
        assert wo.scales.shape == (2, 64, 2)
        assert wo.out_shape == (64,)
        emb = pk["embed"]
        assert emb.bits == 4
        assert emb.codes.shape == (64, 64)          # (V//2, D): gather rows
        assert emb.scales.shape == (128, 2)         # scales stay per row
        # non-quantised leaves pass through untouched
        assert pk["layers"]["norm"] is self.q["layers"]["norm"]

    def test_nibble_packing_halves_code_bytes(self):
        wq = self.packed["layers"]["wq"]
        numel = int(np.prod(wq.shape))
        assert wq.codes.size == numel // 2
        # resident bytes: 0.5 B/code + bf16 scales per block of 32
        assert wq.nbytes_packed == numel // 2 + 2 * wq.scales.size

    def test_dequant_matches_tensor_format_roundtrip(self):
        """PackedTensor.dequantise == TensorFormat.quantise→dequantise,
        exactly (same elementwise ops, reshape only)."""
        for name, get in [
                ("['layers']['wq']", lambda t: t["layers"]["wq"]),
                ("['layers']['wo']", lambda t: t["layers"]["wo"]),
                ("['embed']", lambda t: t["embed"]),
                ("['unembed']", lambda t: t["unembed"])]:
            fmt = self.plan.formats[name]
            ref = fmt.dequantise(fmt.quantise(get(self.params)))
            got = get(self.packed).dequantise()
            assert got.shape == ref.shape and got.dtype == ref.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=name)

    def test_unpack_matches_plan_dequantise(self):
        dense = self.plan.unpack(self.packed)
        ref = self.plan.dequantise(self.q)
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_flatten_with_path(dense)[0],
                jax.tree_util.tree_flatten_with_path(ref)[0]):
            assert path_str(pa) == path_str(pb)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=path_str(pa))

    def test_pack_is_quantise_then_pack(self):
        packed2 = self.plan.pack(self.params, LAYOUTS)
        np.testing.assert_array_equal(
            np.asarray(packed2["layers"]["wq"].codes),
            np.asarray(self.packed["layers"]["wq"].codes))


class TestPackability:
    def test_unpackable_block_size_falls_back_to_dense(self):
        """N=64 does not tile by block=128 → dequantised dense fallback."""
        params = _params()
        plan = build_plan(params, "babsmax128:n4")
        q = plan.quantise(params)
        packed = plan.pack_quantised(q, LAYOUTS)
        wq = packed["layers"]["wq"]
        assert not isinstance(wq, PackedTensor)
        np.testing.assert_array_equal(
            np.asarray(wq),
            np.asarray(plan.formats["['layers']['wq']"].dequantise(
                q["layers"]["wq"])))

    def test_tensor_granularity_not_packable(self):
        params = _params()
        plan = QuantisationPlan(
            {n: parse_format("trms:n4") if n == "['layers']['wq']" else None
             for n, _ in _flat_names(params)})
        packed = plan.pack_quantised(plan.quantise(params), LAYOUTS)
        assert not isinstance(packed["layers"]["wq"], PackedTensor)

    def test_sparse_outliers_not_packable(self):
        params = _params()
        plan = QuantisationPlan(
            {n: parse_format("babsmax32:n4:sp0.01")
             if n == "['layers']['wq']" else None
             for n, _ in _flat_names(params)})
        packed = plan.pack_quantised(plan.quantise(params), LAYOUTS)
        assert not isinstance(packed["layers"]["wq"], PackedTensor)

    def test_no_layout_means_dense(self):
        params = _params()
        plan = QuantisationPlan(
            {n: parse_format("babsmax32:n4") if n == "['layers']['wq']"
             else None for n, _ in _flat_names(params)})
        packed = plan.pack_quantised(plan.quantise(params), {})
        assert not isinstance(packed["layers"]["wq"], PackedTensor)

    def test_int8_packs_uint8(self):
        """256-code formats still fit uint8 codes — one per byte (bits=8
        fall-through; nibble packing is for ≤16-point codebooks only)."""
        params = _params()
        plan = QuantisationPlan(
            {n: parse_format("babsmax32:int8") if n == "['layers']['wq']"
             else None for n, _ in _flat_names(params)})
        packed = plan.pack_quantised(plan.quantise(params), LAYOUTS)
        wq = packed["layers"]["wq"]
        assert isinstance(wq, PackedTensor)
        assert wq.codes.dtype == jnp.uint8
        assert wq.bits == 8 and wq.codes.shape == (2, 64, 64)
        np.testing.assert_array_equal(
            np.asarray(wq.dequantise()),
            np.asarray(plan.formats["['layers']['wq']"].dequantise(
                plan.quantise(params)["layers"]["wq"])))

    def test_17_codepoint_codebook_stays_one_byte_per_code(self):
        """n>16 (here 32-point int5) cannot nibble-pack: bits stays 8."""
        params = _params()
        plan = QuantisationPlan(
            {n: parse_format("babsmax32:int5") if n == "['layers']['wq']"
             else None for n, _ in _flat_names(params)})
        packed = plan.pack_quantised(plan.quantise(params), LAYOUTS)
        wq = packed["layers"]["wq"]
        assert isinstance(wq, PackedTensor)
        assert wq.bits == 8 and wq.k_dim == 64
        assert wq.codes.shape == (2, 64, 64)

    def test_odd_k_falls_through_to_8bit_storage(self):
        """An odd contraction dim has no row to pair: bits=4 is skipped but
        the tensor still serves packed at one byte per code."""
        rng = np.random.default_rng(7)
        params = {"w": jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)}
        plan = QuantisationPlan({"['w']": parse_format("babsmax32:n4")})
        packed = plan.pack_quantised(plan.quantise(params), {"['w']": (0, 1)})
        w = packed["w"]
        assert isinstance(w, PackedTensor)
        assert w.bits == 8 and w.codes.shape == (5, 64)
        fmt = plan.formats["['w']"]
        np.testing.assert_array_equal(
            np.asarray(w.dequantise()),
            np.asarray(fmt.dequantise(fmt.quantise(params["w"]))))


class TestNibbleRoundTrip:
    """Property tests for the K-dim nibble interleave (core.nibble)."""

    @settings(max_examples=30, deadline=None)
    @given(k_half=st.integers(1, 200), n_blocks=st.integers(1, 7),
           lead=st.booleans(), seed=st.integers(0, 2**31 - 1))
    def test_pack_unpack_round_trip(self, k_half, n_blocks, lead, seed):
        """pack→unpack is the identity for any even K, any (odd or even)
        number of N blocks, with or without a leading stack dim."""
        K, N = 2 * k_half, 16 * n_blocks
        shape = (3, K, N) if lead else (K, N)
        rng = np.random.default_rng(seed)
        codes = jnp.asarray(rng.integers(0, 16, shape), jnp.uint8)
        packed = pack_nibbles(codes)
        assert packed.shape == shape[:-2] + (K // 2, N)
        assert packed.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(unpack_nibbles(packed, K)),
                                      np.asarray(codes))

    @settings(max_examples=20, deadline=None)
    @given(k_half=st.integers(1, 300), seed=st.integers(0, 2**31 - 1))
    def test_row_coords_locate_every_row(self, k_half, seed):
        """nibble_row_coords finds each logical row's byte row + nibble
        (the embedding-gather path)."""
        K, N = 2 * k_half, 8
        rng = np.random.default_rng(seed)
        codes = rng.integers(0, 16, (K, N)).astype(np.uint8)
        packed = np.asarray(pack_nibbles(jnp.asarray(codes)))
        rows, nib = nibble_row_coords(np.arange(K), K)
        got = (packed[rows] >> (nib[:, None].astype(np.uint8) * 4)) & 0xF
        np.testing.assert_array_equal(got, codes)

    def test_k_tile_matches_kernel_tile(self):
        """The interleave tile equals the dequant_matmul K tile whenever the
        Pallas kernel could run the shape (so pack layout and in-kernel
        unpack can never disagree)."""
        from repro.kernels.dequant_matmul.dequant_matmul import TILE_K
        for K in (2, 64, 256, 512, 1024):
            t = nibble_k_tile(K)
            assert t == min(TILE_K, K)
            assert K % t == 0 and t % 2 == 0
        # oracle-only shape (K not tiling by TILE_K): one global half-split
        assert nibble_k_tile(300) == 300


def _flat_names(tree):
    return [(path_str(p), x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


class TestPackedMatmulEquivalence:
    def test_linear_matches_dense_einsum(self):
        """layers.linear on a PackedTensor == einsum on its dequantised
        dense tensor (within fp tolerance of the two contraction orders)."""
        from repro.models.layers import linear
        rng = np.random.default_rng(3)
        w = jnp.asarray(rng.standard_normal((64, 2, 32)), jnp.float32)
        fmt = parse_format("babsmax32:n4")
        plan = QuantisationPlan({"['w']": fmt})
        packed = plan.pack_quantised(plan.quantise({"w": w}),
                                     {"['w']": (0, 1)})["w"]
        x = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
        ref = jnp.einsum("btd,dnh->btnh", x, packed.dequantise())
        got = linear(x, packed, "btd,dnh->btnh")
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_embed_lookup_matches_dense_take(self):
        from repro.models.layers import embed_lookup
        rng = np.random.default_rng(4)
        w = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
        fmt = parse_format("babsmax32:n4")
        plan = QuantisationPlan({"['w']": fmt})
        packed = plan.pack_quantised(plan.quantise({"w": w}),
                                     {"['w']": (0, 1)})["w"]
        toks = jnp.asarray(rng.integers(0, 128, (2, 5)), jnp.int32)
        ref = jnp.take(packed.dequantise(), toks, axis=0)
        got = embed_lookup(packed, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)
