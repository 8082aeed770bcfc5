"""Quantised KV cache tests (PR 10): the fused flash-decode kernel body
(interpret mode) against the compositional oracle across linear / windowed /
ring-wrapped caches and ragged multi-token chunks; write-path bit identity
(``quantise_kv`` → kernel dequant == ``block_quant`` → ``block_dequant``);
format parsing + cache-byte accounting; Fisher format allocation; and the
serving stack end to end — per-family greedy drift under q8, the engine
against the single-sequence greedy path with and without a quantised
cache, prefix forks copying quantised rows, and slot-reset isolation."""
import functools
import warnings

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.allocation import allocate_kv_formats, kv_format_bytes
from repro.kernels import ops as kops
from repro.kernels.decode_attention import (decode_attention_quant_ref,
                                            dequant_kv_ref,
                                            unpack_nibbles_hd)
from repro.kernels.decode_attention import decode_attention as daq
from repro.models import api as mapi
from repro.models.layers import QuantisedKV, codebook_bits, quantise_kv
from repro.serve.cache import (KV_FORMATS, build_cache_spec, kv_bits,
                               kv_codebook, parse_kv_formats)
from repro.serve.engine import Request, ServeEngine, greedy_generate
from repro.serve.scheduler import Scheduler

CFG = configs.get_config("paper-100m", "smoke").replace(dtype="float32",
                                                        param_dtype="float32")
ENG_KW = dict(batch_slots=2, kv_len=64, prefill_chunk=4)
PREFIX = [7, 3, 9, 1, 4, 2, 8, 5]
PROMPTS = [PREFIX + [5, 6], PREFIX + [11], PREFIX + [1, 2, 3],
           PREFIX + list(range(10, 19))]


@pytest.fixture(scope="module")
def params():
    fam = mapi.get_family(CFG.family)
    return fam.init(jax.random.PRNGKey(0), CFG)


def _quiet_run(obj, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return obj.run(**kw)


def _run_tokens(eng, prompts, n_new=6):
    for i, p in enumerate(prompts):
        eng.submit(Request(prompt=list(p), max_new_tokens=n_new, rid=i))
    return {g.rid: g.tokens for g in _quiet_run(eng)}


# ---------------------------------------------------------------------------
# Kernel body (interpret mode) vs the compositional oracle
# ---------------------------------------------------------------------------

def _quant_cache(rng, B, S, K, hd, fmt):
    """Random dense cache quantised through the real write path."""
    cb = kv_codebook(fmt)
    dense = jax.random.normal(rng, (B, S, K, hd), jnp.float32)
    codes, scales = quantise_kv(dense, cb, kv_bits(fmt))
    return codes, scales, cb


class TestKernelParity:
    """Pallas kernel (interpret=True forces the kernel body off-TPU)
    against ``decode_attention_quant_ref`` — same codes, same mask
    semantics, per format × cache geometry."""

    def _check(self, fmt, *, B=2, S=24, K=2, H=4, hd=16, T=1,
               window=0, ring=False, positions=None, schunk=None,
               scale=None):
        rng = jax.random.PRNGKey(hash((fmt, S, T, ring)) % 2**31)
        r1, r2, r3 = jax.random.split(rng, 3)
        kc, ks, cb = _quant_cache(r1, B, S, K, hd, fmt)
        vc, vs, _ = _quant_cache(r2, B, S, K, hd, fmt)
        q = jax.random.normal(r3, (B, T, H, hd), jnp.float32)
        if positions is None:
            last = (S - T) if not ring else (S + 3)
            positions = jnp.arange(T)[None, :] + jnp.asarray(
                [[last], [last - (T > 1)]], jnp.int32)[:B]
        bits = kv_bits(fmt)
        got = kops.decode_attention_quant_interpret(
            q, kc, ks, vc, vs, cb, positions, window, ring=ring, bits=bits,
            schunk=schunk, scale=scale)
        want = decode_attention_quant_ref(
            q, kc, ks, vc, vs, cb, positions, window=window, ring=ring,
            bits=bits, scale=scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    def test_linear_decode(self, fmt):
        self._check(fmt)

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    def test_linear_window(self, fmt):
        self._check(fmt, window=7)

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    def test_ring_wrapped(self, fmt):
        # positions past S: slots reconstruct through the wrap
        self._check(fmt, window=8, ring=True)

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    def test_ragged_chunk(self, fmt):
        # T>1 per-slot ragged positions (chunked prefill shape), rows at
        # different depths — includes a row whose chunk starts at 0
        pos = jnp.asarray([[4, 5, 6, 7], [0, 1, 2, 3]], jnp.int32)
        self._check(fmt, T=4, positions=pos)

    def test_schunk_tiling(self):
        # a kv-chunk smaller than S exercises the online-softmax carry
        self._check("q8", S=32, schunk=8)

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    @pytest.mark.parametrize("ring", [False, True])
    def test_ragged_last_chunk(self, fmt, ring):
        # S not a multiple of the kv-chunk (kv_len + prefill slack): the
        # rows past S in the last chunk must be masked, never read
        self._check(fmt, S=30, schunk=8, ring=ring, window=8 if ring else 0)

    @pytest.mark.parametrize("fmt,H,hd", [("q8", 8, 32), ("q8", 16, 32),
                                          ("q4", 8, 64)])
    def test_kv_head_blocks(self, fmt, H, hd, monkeypatch):
        # no block of all 8 KV heads fits a zero budget, so the kernel
        # takes two blocks of 4 (4 x hdc a whole 128-lane row): the
        # head-block grid axis, multi-head (H = K) and grouped (H = 2K)
        monkeypatch.setattr(daq, "VMEM_BUDGET", 0)
        bits = kv_bits(fmt)
        assert daq.choose_kv_block(8, H // 8, 4, hd, bits, 24) == 4
        # no trace made under the default budget may be reused
        daq.decode_attention_quant.clear_cache()
        pos = jnp.asarray([[4, 5, 6, 7], [0, 1, 2, 3]], jnp.int32)
        try:
            self._check(fmt, K=8, H=H, hd=hd, T=4, positions=pos,
                        scale=(hd / 2) ** -0.5)
        finally:
            daq.decode_attention_quant.clear_cache()

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    @pytest.mark.parametrize("layout", ["internlm2_gqa", "zamba2_mha"])
    def test_served_head_layouts(self, fmt, layout, monkeypatch):
        # the head layouts the chip cells serve, at small S: internlm2's
        # grouped 8 KV heads x 2 of 128 in one block, and zamba2's MHA 32
        # heads of 224 with its score scale over several head blocks
        pos = jnp.asarray([[4, 5, 6, 7], [0, 1, 2, 3]], jnp.int32)
        if layout == "internlm2_gqa":
            assert daq.choose_kv_block(8, 2, 4, 128, kv_bits(fmt), 24) == 8
            self._check(fmt, K=8, H=16, hd=128, T=4, positions=pos)
            return
        monkeypatch.setattr(daq, "VMEM_BUDGET", 0)
        assert daq.choose_kv_block(32, 1, 4, 224, kv_bits(fmt), 24) < 32
        daq.decode_attention_quant.clear_cache()
        try:
            self._check(fmt, K=32, H=32, hd=224, T=4, positions=pos,
                        scale=(224 / 2) ** -0.5)
        finally:
            daq.decode_attention_quant.clear_cache()

    def test_scale(self):
        # a score scale other than hd ** -0.5 (Zamba2's (hd / 2) ** -0.5)
        self._check("q8", scale=(16 / 2) ** -0.5)

    def test_traced_window(self):
        # window arrives as a traced scalar inside jitted steps
        pos = jnp.asarray([[20], [19]], jnp.int32)
        self._check("q8", window=jnp.int32(6), positions=pos)


def _subjaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _eqns(sub)


class TestUniformGrid:
    """The kernel decodes a code affinely, ``lo + code · step``: every KV
    codebook must be that grid, a concrete codebook that is not is
    refused, and no codebook select tree is left in the kernel body."""

    @pytest.mark.parametrize("fmt", [f for f in KV_FORMATS if f != "f32"])
    def test_kv_codebook_is_the_affine_grid(self, fmt):
        cb = kv_codebook(fmt)
        lo, step = daq.uniform_grid(cb)
        grid = lo + jnp.arange(cb.shape[0], dtype=jnp.float32) * step
        gap = np.max(np.abs(np.asarray(grid) - np.asarray(cb)))
        assert gap <= np.spacing(np.float32(1.0))

    @pytest.mark.parametrize("interpret", [False, True])
    @pytest.mark.parametrize("n", [16, 256])
    def test_non_uniform_codebook_is_refused(self, n, interpret):
        fmt = "q4" if n == 16 else "q8"
        kc, ks, _ = _quant_cache(jax.random.PRNGKey(0), 1, 8, 1, 16, fmt)
        cb = jnp.linspace(-1.0, 1.0, n, dtype=jnp.float32) ** 3
        q = jnp.ones((1, 1, 2, 16), jnp.float32)
        call = (kops.decode_attention_quant_interpret if interpret
                else kops.decode_attention_quant)
        with pytest.raises(ValueError, match="uniform"):
            call(q, kc, ks, kc, ks, cb, jnp.zeros((1, 1), jnp.int32),
                 bits=kv_bits(fmt))

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    def test_kernel_body_has_no_select_tree(self, fmt):
        # a codebook lookup takes n_codes - 1 selects a part (255 for q8);
        # the affine decode leaves only the masks' few
        kc, ks, cb = _quant_cache(jax.random.PRNGKey(0), 2, 24, 2, 16, fmt)
        q = jnp.ones((2, 1, 4, 16), jnp.float32)
        pos = jnp.zeros((2, 1), jnp.int32)
        outer = jax.make_jaxpr(functools.partial(
            daq.decode_attention_quant, bits=kv_bits(fmt), interpret=True))(
            q, kc, ks, kc, ks, cb, pos)
        body, = [e.params["jaxpr"] for e in _eqns(outer.jaxpr)
                 if e.primitive.name == "pallas_call"]
        selects = sum(e.primitive.name == "select_n" for e in _eqns(body))
        parts = 2 if fmt == "q4" else 1
        assert 0 < selects <= 8 * parts


class TestDequantBitIdentity:
    """The kernel-side dequant must be bit-identical to the block_quant
    reference chain the weight formats use."""

    def test_nibble_pack_roundtrip(self):
        codes = jnp.arange(16, dtype=jnp.uint8).reshape(1, 16)
        packed = codes[..., 0::2] | (codes[..., 1::2] << jnp.uint8(4))
        np.testing.assert_array_equal(np.asarray(unpack_nibbles_hd(packed)),
                                      np.asarray(codes))

    @pytest.mark.parametrize("fmt", ["q8", "q4"])
    def test_write_read_matches_block_quant(self, fmt):
        B, T, K, hd = 2, 5, 3, 16
        cb = kv_codebook(fmt)
        new = jax.random.normal(jax.random.PRNGKey(3), (B, T, K, hd),
                                jnp.float32)
        codes, scales = quantise_kv(new, cb, kv_bits(fmt))
        got = dequant_kv_ref(codes, scales, cb, kv_bits(fmt))
        # reference: the weight-format pipeline on the same rows
        rows = new.reshape(B * T * K, hd)
        pad = (-rows.shape[0]) % 256 if rows.shape[0] > 256 else 0
        rc, rs = kops.block_quant(jnp.pad(rows, ((0, pad), (0, 0))), cb,
                                  block=hd)
        want = kops.block_dequant(rc, rs, cb, block=hd,
                                  dtype=jnp.float32)[:B * T * K]
        np.testing.assert_array_equal(np.asarray(got).reshape(-1, hd),
                                      np.asarray(want))

    def test_codebook_bits(self):
        assert codebook_bits(kv_codebook("q4")) == 4
        assert codebook_bits(kv_codebook("q8")) == 8

    def test_zero_scale_row_dequantises_to_zero(self):
        # a reset-wiped row (codes 0, scale 0) must read as the dense
        # wipe (0.0) regardless of codebook content
        cb = kv_codebook("q8")
        z = dequant_kv_ref(jnp.zeros((1, 4, 1, 8), jnp.uint8),
                           jnp.zeros((1, 4, 1, 1), jnp.float32), cb, 8)
        assert not np.asarray(z).any()


# ---------------------------------------------------------------------------
# Formats, geometry, accounting, allocation
# ---------------------------------------------------------------------------

class TestFormatsAndAccounting:
    def test_parse_broadcast_and_per_group(self):
        assert parse_kv_formats("", 3, 64) == ("f32", "f32", "f32")
        assert parse_kv_formats("q8", 3, 64) == ("q8", "q8", "q8")
        assert parse_kv_formats("f32,q8,q4", 3, 64) == ("f32", "q8", "q4")

    def test_parse_rejects_bad_input(self):
        with pytest.raises(ValueError):
            parse_kv_formats("q5", 1, 64)
        with pytest.raises(ValueError):
            parse_kv_formats("q8,q4", 3, 64)     # wrong count
        with pytest.raises(ValueError):
            parse_kv_formats("q4", 1, 63)        # odd hd can't nibble-pack

    def test_state_specs_geometry(self):
        cfg = CFG.replace(kv_format="q4")
        fam = mapi.get_family(cfg.family)
        spec = fam.cache_spec(cfg, 2, 32, 4, True)
        ss = spec.state_specs()
        for g in spec.groups:
            assert g.quantised and g.fmt == "q4"
            assert ss[g.k_key].dtype == "uint8"
            assert ss[g.k_key].shape[-1] == cfg.hd // 2   # nibble-packed
            assert ss[g.k_scale_key].dtype == "float32"
            assert ss[g.k_scale_key].shape[-1] == 1
            assert g.k_scale_key in spec.state_keys
            assert g.v_scale_key in spec.state_keys

    def test_q8_cache_ratio_meets_gate(self):
        # f32 dense baseline (dtype float32): q8 = (1 + 4/hd) / 4 per
        # element — the ≤ 0.35× acceptance gate with margin at hd ≥ 16
        cfg = CFG.replace(kv_format="q8")
        fam = mapi.get_family(cfg.family)
        cb = fam.cache_spec(cfg, 2, 64, 4, True).cache_bytes()
        want = kv_format_bytes("q8", cfg.hd) / 4.0
        assert cb["cache_ratio_vs_dense"] == pytest.approx(want, abs=1e-4)
        assert cb["cache_ratio_vs_dense"] <= 0.35
        assert cb["code_bytes"] > 0 and cb["scale_bytes"] > 0
        assert cb["kv"] == cb["code_bytes"] + cb["scale_bytes"]

    def test_allocate_kv_formats_demotes_least_sensitive_first(self):
        stats = {
            "g0": dict(numel=1000, rms=1.0, fisher_mean=1.0),   # sensitive
            "g1": dict(numel=1000, rms=1e-3, fisher_mean=1e-6),
        }
        full = 2000 * 4.0
        # budget between all-f32 and one-group-q8: only g1 demotes
        fmts = allocate_kv_formats(stats, full - 1, head_dim=64)
        assert fmts == {"g0": "f32", "g1": "q8"}
        # tight budget walks the whole ladder
        tight = 2000 * kv_format_bytes("q4", 64) + 1
        assert set(allocate_kv_formats(stats, tight, 64).values()) == {"q4"}
        with pytest.raises(ValueError):
            allocate_kv_formats(stats, 10.0, 64)   # under all-q4 floor


# ---------------------------------------------------------------------------
# Serving end to end
# ---------------------------------------------------------------------------

FAMILY_SMOKE = ["paper-100m", "gemma3-1b", "whisper-large-v3",
                "zamba2-2.7b", "internvl2-26b"]


class TestGreedyDrift:
    """q8 greedy decode tracks the dense cache at smoke scale on every
    attention family. Random-init logits have argmax near-ties, so a lone
    flipped token is tolerated; systematic drift (the thing a broken
    dequant or mask produces) is not. The serve bench gates the trained
    full config at ≤5%."""

    @pytest.mark.parametrize("arch", FAMILY_SMOKE)
    def test_q8_drift_bounded(self, arch):
        cfg = configs.get_config(arch, "smoke").replace(
            dtype="float32", param_dtype="float32")
        fam = mapi.get_family(cfg.family)
        p = fam.init(jax.random.PRNGKey(0), cfg)
        prompt = np.asarray([[5, 3, 11, 2, 7, 1]], np.int32)
        dense = greedy_generate(cfg, p, prompt, 8, kv_len=32)
        quant = greedy_generate(cfg.replace(kv_format="q8"), p, prompt, 8,
                                kv_len=32)
        drift = int((dense != quant).sum())
        assert drift <= 1, f"{arch}: q8 drifted {drift}/8 tokens"

    def test_q4_decodes(self, params):
        # q4 is exercised for liveness, not bit-equality: argmax near-ties
        # under random init make greedy drift expected (the bench reports
        # it; the kernel-parity tests above pin its numerics)
        cfg = CFG.replace(kv_format="q4")
        out = greedy_generate(cfg, params,
                              np.asarray([[5, 3, 11, 2]], np.int32), 6,
                              kv_len=32)
        assert out.shape == (1, 6)


class TestEngineQuantised:
    @pytest.mark.parametrize("kv_format", ["", "q8"])
    def test_q8_engine_matches_greedy(self, params, kv_format):
        """The batched engine agrees with the single-sequence greedy path
        under the same cache format; the dense cache ("") allocates what
        an engine on the dense config does, q8 less."""
        cfg = CFG.replace(kv_format=kv_format)
        eng = ServeEngine(cfg, params, **ENG_KW)
        done = _run_tokens(eng, PROMPTS)
        for i, p in enumerate(PROMPTS):
            ref = greedy_generate(cfg, params,
                                  np.asarray([p], np.int32), 6, kv_len=64)
            assert done[i] == list(ref[0]), f"prompt {i} diverged"
        dense_cb = ServeEngine(CFG, params, **ENG_KW).cache_bytes()
        if kv_format:
            assert eng.cache_bytes()["kv"] < dense_cb["kv"]
        else:
            assert eng.cache_bytes() == dense_cb

    def test_prefix_fork_quantised(self, params):
        """PrefixPool forks copy quantised code + scale rows verbatim:
        forked tokens == full recompute, with a prefill saving."""
        cfg_q = CFG.replace(kv_format="q8")
        make = lambda: ServeEngine(cfg_q, params, **ENG_KW)  # noqa: E731
        ref_eng = make()
        ref = _run_tokens(ref_eng, PROMPTS)
        eng = make()
        sched = Scheduler(eng)
        sched.register_prefix("sys", PREFIX)
        for i, p in enumerate(PROMPTS):
            sched.submit(list(p), max_new_tokens=6, prefix="sys", rid=i)
        done = {g.rid: g.tokens for g in _quiet_run(sched)}
        assert done == ref
        total = eng.prefill_slot_steps + sched.pool.prefill_steps
        assert total < ref_eng.prefill_slot_steps

    def test_slot_reset_isolates_requests(self, params):
        """A reused slot must not leak the predecessor's quantised rows:
        the same request decodes identically on a fresh engine and after
        another request ran in the slot (reset wipes codes AND scales)."""
        cfg_q = CFG.replace(kv_format="q8")
        kw = dict(ENG_KW, batch_slots=1)
        probe = [9, 2, 4, 4, 1]
        fresh = _run_tokens(ServeEngine(cfg_q, params, **kw), [probe])
        eng = ServeEngine(cfg_q, params, **kw)
        both = _run_tokens(eng, [list(range(12, 24)), probe])
        assert both[1] == fresh[0]
