"""Serving tests: engine generation, quantised-weight serving, and the
context-parallel flash-decode combine math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import build_plan
from repro.models import api as mapi
from repro.serve.context_parallel import combine_partials, partial_attention
from repro.serve.engine import Request, ServeEngine, greedy_generate

CFG = configs.get_config("paper-100m", "smoke").replace(dtype="float32",
                                                        param_dtype="float32")


def _params():
    fam = mapi.get_family(CFG.family)
    return fam.init(jax.random.PRNGKey(0), CFG)


class TestEngine:
    def test_greedy_matches_forward_argmax(self):
        params = _params()
        fam = mapi.get_family(CFG.family)
        prompt = np.asarray([[5, 9, 3, 7]], np.int32)
        gen = greedy_generate(CFG, params, prompt, n_new=3, kv_len=16)
        # reference: iterative full forward
        toks = prompt.copy()
        for _ in range(3):
            logits = fam.apply(params, {"tokens": jnp.asarray(toks)}, CFG)
            nxt = np.asarray(jnp.argmax(logits[:, -1], -1))[:, None]
            toks = np.concatenate([toks, nxt], 1)
        np.testing.assert_array_equal(gen, toks[:, prompt.shape[1]:])

    def test_engine_batched_same_prompt(self):
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=2, kv_len=32)
        for rid in range(2):
            eng.submit(Request(prompt=[5, 9, 3, 7], max_new_tokens=4,
                               rid=rid))
        done = eng.run()
        assert len(done) == 2
        assert all(len(g.tokens) == 4 for g in done)
        assert done[0].tokens == done[1].tokens  # same prompt → same output
        ref = greedy_generate(CFG, params, np.asarray([[5, 9, 3, 7]]),
                              n_new=4, kv_len=32)
        assert done[0].tokens == list(ref[0])

    def test_quantised_weight_serving_close_to_bf16(self):
        params = _params()
        plan = build_plan(params, "babsmax128:int8")
        qparams = plan.quantise(params)
        eng_q = ServeEngine.from_quantised(CFG, qparams, plan,
                                           batch_slots=1, kv_len=32)
        eng_f = ServeEngine(CFG, params, batch_slots=1, kv_len=32)
        for eng in (eng_q, eng_f):
            eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
        a = eng_q.run()[0].tokens
        b = eng_f.run()[0].tokens
        # int8 weights: greedy tokens should mostly agree on a tiny model
        assert sum(x == y for x, y in zip(a, b)) >= 2


class TestStepBudgetExpiry:
    """``run(max_steps)`` expiring with live work must be loud (warning),
    lossless (partials returned with ``done=False``), and resumable."""

    def test_warns_returns_partials_and_resumes(self):
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=32,
                          prefill_chunk=4)
        eng.submit(Request(prompt=[5, 9, 3, 7], max_new_tokens=6, rid=0))
        with pytest.warns(RuntimeWarning, match="max_steps=2 expired"):
            partial = eng.run(max_steps=2)
        assert len(partial) == 1 and not partial[0].done
        got = list(partial[0].tokens)
        assert len(got) < 6
        # a second run() continues the live slot to completion
        done = eng.run()
        assert len(done) == 1 and done[0].done
        assert done[0].tokens[:len(got)] == got
        ref = greedy_generate(CFG, params, np.asarray([[5, 9, 3, 7]]),
                              n_new=6, kv_len=32)
        assert done[0].tokens == list(ref[0])

    def test_warns_when_queue_still_pending(self):
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=32,
                          prefill_chunk=4)
        for rid in range(2):            # second request can never be seated
            eng.submit(Request(prompt=[5, 9, 3], max_new_tokens=4, rid=rid))
        with pytest.warns(RuntimeWarning, match="1 queued"):
            eng.run(max_steps=3)

    def test_no_warning_when_drained(self):
        import warnings as _w
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=32)
        eng.submit(Request(prompt=[5, 9, 3], max_new_tokens=2, rid=0))
        with _w.catch_warnings():
            _w.simplefilter("error", RuntimeWarning)
            done = eng.run()
        assert len(done) == 1 and done[0].done


class TestDecodeStateAlloc:
    """Engine and :func:`greedy_generate` allocate decode state through the
    one shared spec→zeros helper, so their cache geometry cannot drift."""

    def test_engine_zero_state_matches_helper(self):
        from repro.serve.engine import alloc_decode_state
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=2, kv_len=32,
                          prefill_chunk=4)
        fam = mapi.get_family(CFG.family)
        helper = alloc_decode_state(fam, CFG, 2, 32, slack=4,
                                    windowed=eng.windowed_cache)
        a = jax.tree.map(lambda x: (x.shape, str(x.dtype)), eng._zero_state())
        b = jax.tree.map(lambda x: (x.shape, str(x.dtype)), helper)
        assert a == b

    def test_slack_extends_cache(self):
        """slack=chunk buys spill rows past kv_len (greedy_generate's
        single-token steps need only slack=1)."""
        from repro.serve.engine import alloc_decode_state
        fam = mapi.get_family(CFG.family)
        n = lambda s: sum(int(x.size) for x in jax.tree.leaves(
            alloc_decode_state(fam, CFG, 1, 16, slack=s)))
        assert n(8) > n(1)


class TestWeightBytesCodebooks:
    def test_codebook_bytes_track_stored_dtype(self, monkeypatch):
        """Codebooks are sized at the dtype of the array the kernel reads,
        not an assumed 4 bytes per codepoint."""
        from repro.core import tensor_format
        params = _params()
        plan = build_plan(params, "babsmax32:n4")
        eng = ServeEngine.from_quantised(CFG, plan.quantise(params), plan,
                                         batch_slots=1, kv_len=16)
        base = eng.weight_bytes()
        assert base["codebooks"] > 0
        orig = tensor_format.PackedTensor.codebook
        monkeypatch.setattr(tensor_format.PackedTensor, "codebook",
                            lambda self: orig(self).astype(jnp.bfloat16))
        assert eng.weight_bytes()["codebooks"] * 2 == base["codebooks"]


class TestPackedServing:
    """The tentpole: serve directly from packed quantised weights."""

    def _engines(self, **kw):
        params = _params()
        plan = build_plan(params, "babsmax32:n4")
        qparams = plan.quantise(params)
        eng_p = ServeEngine.from_quantised(CFG, qparams, plan, **kw)
        eng_d = ServeEngine.from_quantised(CFG, qparams, plan, packed=False,
                                           **kw)
        return eng_p, eng_d, plan

    def test_all_planned_tensors_held_packed(self):
        """No dequantised bf16/f32 copy for any planned tensor: uint8 codes
        + block scales only."""
        from repro.core import PackedTensor
        from repro.core.plan import path_str
        eng_p, _, plan = self._engines(batch_slots=1, kv_len=32)
        flat = jax.tree_util.tree_flatten_with_path(
            eng_p.params, is_leaf=lambda x: isinstance(x, PackedTensor))[0]
        n_packed = 0
        for p, leaf in flat:
            if plan.formats.get(path_str(p)) is not None:
                assert isinstance(leaf, PackedTensor), path_str(p)
                assert leaf.codes.dtype == jnp.uint8
                # n4 = 16 codepoints → nibble-packed, two codes per byte
                assert leaf.bits == 4, path_str(p)
                assert leaf.codes.size * 2 == int(np.prod(leaf.shape))
                n_packed += 1
        assert n_packed >= 8  # every matmul weight + embed on paper-100m

    def test_packed_weight_bytes_shrink(self):
        eng_p, eng_d, _ = self._engines(batch_slots=1, kv_len=32)
        wb_p, wb_d = eng_p.weight_bytes(), eng_d.weight_bytes()
        assert wb_p["packed"] > 0 and wb_d["packed"] == 0
        # nibble-packed 4-bit codes + bf16/32-block scales ≈ 4.5 resident
        # bits vs the 32-bit master copy — the paper's full ~4× cut over
        # bf16 (~7× vs f32; was 0.26× before sub-byte packing)
        assert wb_p["total"] < 0.16 * wb_d["total"]

    def test_packed_decode_identical_greedy_tokens(self):
        """Packed 4-bit engine == dequantised engine: same greedy tokens."""
        eng_p, eng_d, _ = self._engines(batch_slots=2, kv_len=32,
                                        prefill_chunk=4)
        for eng in (eng_p, eng_d):
            eng.submit(Request(prompt=[5, 9, 3, 7, 2], max_new_tokens=6,
                               rid=0))
            eng.submit(Request(prompt=[11, 4], max_new_tokens=6, rid=1))
        a = {g.rid: g.tokens for g in eng_p.run()}
        b = {g.rid: g.tokens for g in eng_d.run()}
        assert a == b

    def test_packed_decode_logits_close(self):
        """Step-level logits of packed vs dequantised params agree to fp
        tolerance (same quantised values, different contraction order)."""
        params = _params()
        plan = build_plan(params, "babsmax32:n4")
        qparams = plan.quantise(params)
        fam = mapi.get_family(CFG.family)
        packed = plan.pack_quantised(qparams, fam.pack_layouts(CFG))
        dense = plan.dequantise(qparams)
        # grouped decode-state protocol: pure-global = one group k0/v0
        state = {
            "k0": jnp.zeros((CFG.n_layers, 1, 16, CFG.n_kv_heads, CFG.hd)),
            "v0": jnp.zeros((CFG.n_layers, 1, 16, CFG.n_kv_heads, CFG.hd)),
            "pos": jnp.zeros((1,), jnp.int32),
        }
        batch = {"tokens": jnp.asarray([[7]], jnp.int32)}
        lp, _ = fam.decode_step(packed, state, batch, CFG)
        ld, _ = fam.decode_step(dense, state, batch, CFG)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                   rtol=2e-4, atol=2e-4)


class TestMoEPackedServing:
    """MoE expert stacks serve packed (dequant_matmul's batched lead dim)
    instead of being densified at load."""

    MCFG = configs.get_config("qwen2-moe-a2.7b", "smoke").replace(
        dtype="float32", param_dtype="float32")

    def _engines(self, **kw):
        fam = mapi.get_family(self.MCFG.family)
        params = fam.init(jax.random.PRNGKey(0), self.MCFG)
        plan = build_plan(params, "babsmax16:n4")  # d_expert=48 tiles by 16
        qparams = plan.quantise(params)
        eng_p = ServeEngine.from_quantised(self.MCFG, qparams, plan, **kw)
        eng_d = ServeEngine.from_quantised(self.MCFG, qparams, plan,
                                           packed=False, **kw)
        return eng_p, eng_d

    def test_expert_stacks_held_packed(self):
        from repro.core import PackedTensor
        from repro.core.plan import path_str
        eng_p, _ = self._engines(batch_slots=1, kv_len=32)
        flat = jax.tree_util.tree_flatten_with_path(
            eng_p.params, is_leaf=lambda x: isinstance(x, PackedTensor))[0]
        leaves = {path_str(p): l for p, l in flat}
        for name in ("we_gate", "we_up", "we_down",
                     "ws_gate", "ws_up", "ws_down"):
            leaf = leaves[f"['layers']['{name}']"]
            assert isinstance(leaf, PackedTensor), name
            assert leaf.bits == 4, name
        # router stays dense: it feeds top-k dispatch, not a layers.linear
        assert not isinstance(leaves["['layers']['w_router']"], PackedTensor)

    def test_moe_packed_greedy_tokens_identical(self):
        eng_p, eng_d = self._engines(batch_slots=2, kv_len=32,
                                     prefill_chunk=4)
        for eng in (eng_p, eng_d):
            eng.submit(Request(prompt=[5, 9, 3, 7], max_new_tokens=6, rid=0))
            eng.submit(Request(prompt=[11, 4], max_new_tokens=6, rid=1))
        a = {g.rid: g.tokens for g in eng_p.run()}
        b = {g.rid: g.tokens for g in eng_d.run()}
        assert a == b


class TestUnifiedPackedFamilies:
    """The unified projection API: rwkv6 / zamba2 / whisper serve packed
    through `layers.linear` exactly like the transformer — greedy tokens
    identical to the dequantised-dense engine, with the big projections
    held as PackedTensors. Both engines now run the ragged path (per-slot
    positions + chunked prefill through the block-parallel wkv/ssd forms),
    so this doubles as packed-vs-dense parity for the new ragged paths."""

    FAMS = {
        "rwkv6-1.6b": ("['layers']['wr']", 10),
        "zamba2-2.7b": ("['mamba'][0]['out_proj']", 8),
        "whisper-large-v3": ("['dec']['self_wq']", 14),
    }

    def _engines(self, arch, **kw):
        cfg = configs.get_config(arch, "smoke").replace(
            dtype="float32", param_dtype="float32")
        fam = mapi.get_family(cfg.family)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        plan = build_plan(params, "babsmax32:n4")
        qparams = plan.quantise(params)
        eng_p = ServeEngine.from_quantised(cfg, qparams, plan, **kw)
        eng_d = ServeEngine.from_quantised(cfg, qparams, plan, packed=False,
                                           **kw)
        return eng_p, eng_d

    @pytest.mark.parametrize("arch", list(FAMS))
    def test_projections_held_packed(self, arch):
        from repro.core import PackedTensor
        from repro.core.plan import path_str
        probe, n_min = self.FAMS[arch]
        eng_p, _ = self._engines(arch, batch_slots=1, kv_len=32)
        flat = jax.tree_util.tree_flatten_with_path(
            eng_p.params, is_leaf=lambda x: isinstance(x, PackedTensor))[0]
        leaves = {path_str(p): l for p, l in flat}
        assert isinstance(leaves[probe], PackedTensor), probe
        assert leaves[probe].bits == 4
        n_packed = sum(1 for l in leaves.values()
                       if isinstance(l, PackedTensor))
        assert n_packed >= n_min, (arch, n_packed)
        # the embedding table is always packed (gather + tied-transposed use)
        assert isinstance(leaves["['embed']"], PackedTensor)

    @pytest.mark.parametrize("arch", list(FAMS))
    def test_packed_greedy_tokens_identical(self, arch):
        eng_p, eng_d = self._engines(arch, batch_slots=2, kv_len=32,
                                     prefill_chunk=4)
        for eng in (eng_p, eng_d):
            eng.submit(Request(prompt=[5, 9, 3, 7], max_new_tokens=6, rid=0))
            eng.submit(Request(prompt=[11, 4], max_new_tokens=6, rid=1))
        a = {g.rid: g.tokens for g in eng_p.run()}
        b = {g.rid: g.tokens for g in eng_d.run()}
        assert set(a) == {0, 1} and a == b


class TestTiedEmbeddingServing:
    """tie_embeddings: the packed (V, D) embed table serves BOTH the token
    gather and the logits matmul (transposed kernel variant) — no dense
    unembed is ever materialised."""

    TCFG = CFG.replace(tie_embeddings=True)

    def _engines(self, **kw):
        fam = mapi.get_family(self.TCFG.family)
        params = fam.init(jax.random.PRNGKey(0), self.TCFG)
        assert "unembed" not in params   # tied: no separate table exists
        plan = build_plan(params, "babsmax32:n4")
        qparams = plan.quantise(params)
        eng_p = ServeEngine.from_quantised(self.TCFG, qparams, plan, **kw)
        eng_d = ServeEngine.from_quantised(self.TCFG, qparams, plan,
                                           packed=False, **kw)
        return eng_p, eng_d

    def test_embed_packed_no_dense_unembed(self):
        from repro.core import PackedTensor
        eng_p, _ = self._engines(batch_slots=1, kv_len=32)
        emb = eng_p.params["embed"]
        assert isinstance(emb, PackedTensor) and emb.bits == 4
        assert "unembed" not in eng_p.params
        # nothing vocab-sized is resident dense: only norms remain unpacked
        for leaf in jax.tree.leaves(
                eng_p.params, is_leaf=lambda x: isinstance(x, PackedTensor)):
            if not isinstance(leaf, PackedTensor):
                assert self.TCFG.vocab not in leaf.shape, leaf.shape

    def test_tied_packed_greedy_tokens_identical(self):
        eng_p, eng_d = self._engines(batch_slots=2, kv_len=32,
                                     prefill_chunk=4)
        for eng in (eng_p, eng_d):
            eng.submit(Request(prompt=[5, 9, 3, 7, 2], max_new_tokens=6,
                               rid=0))
            eng.submit(Request(prompt=[11, 4], max_new_tokens=6, rid=1))
        a = {g.rid: g.tokens for g in eng_p.run()}
        b = {g.rid: g.tokens for g in eng_d.run()}
        assert a == b

    def test_tied_decode_matches_apply_argmax(self):
        """Tied decode path (transposed linear) against the forward pass."""
        fam = mapi.get_family(self.TCFG.family)
        params = fam.init(jax.random.PRNGKey(1), self.TCFG)
        prompt = np.asarray([[5, 9, 3, 7]], np.int32)
        gen = greedy_generate(self.TCFG, params, prompt, n_new=3, kv_len=16)
        toks = prompt.copy()
        for _ in range(3):
            logits = fam.apply(params, {"tokens": jnp.asarray(toks)},
                               self.TCFG)
            nxt = np.asarray(jnp.argmax(logits[:, -1], -1))[:, None]
            toks = np.concatenate([toks, nxt], 1)
        np.testing.assert_array_equal(gen, toks[:, prompt.shape[1]:])


class TestEmptyPackLayoutFailFast:
    def test_packed_engine_refuses_empty_layout_family(self):
        """A family declaring empty_pack_layouts must fail fast on
        packed=True (never silently serve dense)."""
        from repro.models.api import (ModelFamily, empty_pack_layouts,
                                      register_family, _FAMILIES)
        fam = mapi.get_family(CFG.family)
        stub = ModelFamily(
            name="_nopack_stub", param_specs=fam.param_specs, init=fam.init,
            apply=fam.apply, decode_state_specs=fam.decode_state_specs,
            decode_step=fam.decode_step, prefill=fam.prefill,
            supports_ragged=True, pack_layouts=empty_pack_layouts)
        register_family(stub)
        try:
            cfg = CFG.replace(family="_nopack_stub")
            params = _params()
            plan = build_plan(params, "babsmax32:n4")
            with pytest.raises(ValueError, match="_nopack_stub"):
                ServeEngine.from_quantised(cfg, plan.quantise(params), plan,
                                           batch_slots=1, kv_len=32)
            # the explicit opt-out still works
            eng = ServeEngine.from_quantised(cfg, plan.quantise(params), plan,
                                             packed=False, batch_slots=1,
                                             kv_len=32)
            assert eng.weight_bytes()["packed"] == 0
        finally:
            _FAMILIES.pop("_nopack_stub", None)

    def test_pack_layouts_required_at_registration(self):
        from repro.models.api import ModelFamily
        with pytest.raises(ValueError, match="pack_layouts"):
            ModelFamily(name="_bad", param_specs=None, init=None, apply=None)


class TestRaggedSlots:
    """Per-slot KV positions: slots with different prompt lengths decode
    correctly in one batch, each matching its single-sequence reference."""

    def test_ragged_prompts_match_single_sequence_reference(self):
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=3, kv_len=32,
                          prefill_chunk=4)
        prompts = {0: [5, 9, 3, 7, 2, 8, 1], 1: [11, 4], 2: [3, 3, 3, 3]}
        for rid, p in prompts.items():
            eng.submit(Request(prompt=p, max_new_tokens=5, rid=rid))
        done = {g.rid: g.tokens for g in eng.run()}
        assert set(done) == set(prompts)
        for rid, p in prompts.items():
            ref = greedy_generate(CFG, params, np.asarray([p]), n_new=5,
                                  kv_len=32)
            assert done[rid] == list(ref[0]), f"rid={rid}"

    def test_continuous_batching_replaces_finished_ragged_slots(self):
        """More requests than slots, ragged lengths: all finish and match."""
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=2, kv_len=32,
                          prefill_chunk=4)
        prompts = {0: [1, 2, 3], 1: [9, 8, 7, 6, 5], 2: [4], 3: [2, 2]}
        for rid, p in prompts.items():
            eng.submit(Request(prompt=p, max_new_tokens=4, rid=rid))
        done = {g.rid: g.tokens for g in eng.run()}
        assert set(done) == set(prompts)
        for rid, p in prompts.items():
            ref = greedy_generate(CFG, params, np.asarray([p]), n_new=4,
                                  kv_len=32)
            assert done[rid] == list(ref[0]), f"rid={rid}"


class TestChunkedPrefill:
    def test_chunked_prefill_equals_token_by_token(self):
        """prefill_chunk>1 must not change any generated token vs chunk=1
        (token-by-token prefill)."""
        params = _params()
        prompts = {0: [5, 9, 3, 7, 2, 8, 1, 6, 4], 1: [11, 4, 7]}
        outs = {}
        for chunk in (1, 4):
            eng = ServeEngine(CFG, params, batch_slots=2, kv_len=32,
                              prefill_chunk=chunk)
            for rid, p in prompts.items():
                eng.submit(Request(prompt=p, max_new_tokens=6, rid=rid))
            outs[chunk] = {g.rid: g.tokens for g in eng.run()}
        assert outs[1] == outs[4]

    def test_prefill_chunk_larger_than_prompt(self):
        params = _params()
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=32,
                          prefill_chunk=16)
        eng.submit(Request(prompt=[5, 9, 3], max_new_tokens=4, rid=0))
        done = eng.run()
        ref = greedy_generate(CFG, params, np.asarray([[5, 9, 3]]), n_new=4,
                              kv_len=32)
        assert done[0].tokens == list(ref[0])


class TestContextParallel:
    def test_combine_partials_exact(self):
        """Sharded partial-softmax combine == monolithic attention."""
        rng = np.random.default_rng(0)
        B, S, K, G, hd = 2, 64, 2, 2, 8
        H = K * G
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
        q_pos = 40  # only the first 41 positions visible

        n_shards = 4
        S_loc = S // n_shards
        parts = []
        for i in range(n_shards):
            kv_pos = jnp.arange(i * S_loc, (i + 1) * S_loc)
            parts.append(partial_attention(
                q, k[:, i * S_loc:(i + 1) * S_loc],
                v[:, i * S_loc:(i + 1) * S_loc], kv_pos, q_pos))
        m = jnp.stack([p[0] for p in parts])
        l = jnp.stack([p[1] for p in parts])
        acc = jnp.stack([p[2] for p in parts])
        out = combine_partials(m, l, acc)

        from repro.models.layers import decode_attention
        ref = decode_attention(q, k, v, q_pos).reshape(B, K, G, hd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_combine_with_fully_masked_shard(self):
        """Shards past the current position contribute nothing (no NaNs)."""
        rng = np.random.default_rng(1)
        B, S, K, G, hd = 1, 32, 1, 1, 4
        q = jnp.asarray(rng.standard_normal((B, 1, K * G, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
        q_pos = 7  # second half fully masked
        parts = [partial_attention(q, k[:, :16], v[:, :16],
                                   jnp.arange(16), q_pos),
                 partial_attention(q, k[:, 16:], v[:, 16:],
                                   jnp.arange(16, 32), q_pos)]
        m = jnp.stack([p[0] for p in parts])
        l = jnp.stack([p[1] for p in parts])
        acc = jnp.stack([p[2] for p in parts])
        out = combine_partials(m, l, acc)
        assert bool(jnp.isfinite(out).all())
        from repro.models.layers import decode_attention
        ref = decode_attention(q, k, v, q_pos).reshape(B, K, G, hd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_cp_decode_attention_single_device_mesh(self):
        """shard_map path on a 1-device mesh == plain decode attention."""
        from repro.serve.context_parallel import cp_decode_attention
        from repro.models.layers import decode_attention
        mesh = jax.make_mesh((1,), ("data",))
        rng = np.random.default_rng(2)
        B, S, H, hd = 1, 32, 4, 8
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        with mesh:
            out = jax.jit(lambda q, k, v: cp_decode_attention(
                q, k, v, 10, mesh, "data"))(q, k, v)
        ref = decode_attention(q, k, v, 10)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
