"""Scheduler front-end tests: shared-prefix fork correctness per family
(dense transformer, gemma3 ring-cache groups, packed checkpoint), pool
eviction under live forks, priority/fairness admission, the submit/stream
lifecycle and latency stamps, expiry accounting under mid-wave admission,
and deterministic replay of a fixed arrival list (plain and
fault-injected)."""
import warnings

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import build_plan
from repro.models import api as mapi
from repro.serve import faults
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import Scheduler

CFG = configs.get_config("paper-100m", "smoke").replace(dtype="float32",
                                                        param_dtype="float32")
ENG_KW = dict(batch_slots=2, kv_len=64, prefill_chunk=4)
PREFIX = [7, 3, 9, 1, 4, 2, 8, 5]          # shared 8-token system prompt
PROMPTS = [PREFIX + [5, 6], PREFIX + [11], PREFIX + [1, 2, 3],
           PREFIX + list(range(10, 19))]   # last one crosses chunk bounds


@pytest.fixture(scope="module")
def params():
    fam = mapi.get_family(CFG.family)
    return fam.init(jax.random.PRNGKey(0), CFG)


def _quiet_run(obj, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return obj.run(**kw)


def _recompute_tokens(cfg, make_engine, prompts, n_new=5):
    """Reference: same prompts through a plain engine (no scheduler, no
    prefix declaration) — full recomputation."""
    eng = make_engine()
    for i, p in enumerate(prompts):
        eng.submit(Request(prompt=list(p), max_new_tokens=n_new, rid=i))
    done = _quiet_run(eng)
    return {g.rid: g.tokens for g in done}, eng.prefill_slot_steps


def _fork_vs_recompute(cfg, make_engine, prompts, prefix, n_new=5):
    ref, ref_prefill = _recompute_tokens(cfg, make_engine, prompts, n_new)
    eng = make_engine()
    sched = Scheduler(eng)
    sched.register_prefix("sys", prefix)
    for i, p in enumerate(prompts):
        sched.submit(list(p), max_new_tokens=n_new, prefix="sys", rid=i)
    done = {g.rid: g.tokens for g in _quiet_run(sched)}
    assert done == ref, "forked-prefix tokens differ from recompute"
    total = eng.prefill_slot_steps + sched.pool.prefill_steps
    assert total < ref_prefill, (
        f"no prefill saving: {total} >= {ref_prefill} slot-steps")
    assert sched.stats["forks"] == len(prompts)


class TestPrefixForkPerFamily:
    def test_transformer_dense(self, params):
        _fork_vs_recompute(
            CFG, lambda: ServeEngine(CFG, params, **ENG_KW),
            PROMPTS, PREFIX)

    def test_gemma3_ring_groups(self):
        # 5:1 local(16):global — the fork must copy ring-buffer rows and
        # full-length global rows alike; prompts long enough that the
        # prefix occupies real ring slots
        cfg = configs.get_config("gemma3-1b", "smoke").replace(
            dtype="float32", param_dtype="float32")
        fam = mapi.get_family(cfg.family)
        p = fam.init(jax.random.PRNGKey(1), cfg)
        _fork_vs_recompute(
            cfg, lambda: ServeEngine(cfg, p, **ENG_KW), PROMPTS, PREFIX)

    def test_packed_checkpoint(self, params):
        plan = build_plan(params, "babsmax32:n4")
        q = plan.quantise(params)
        _fork_vs_recompute(
            CFG, lambda: ServeEngine.from_quantised(CFG, q, plan, **ENG_KW),
            PROMPTS, PREFIX)

    def test_prompt_equal_to_prefix(self, params):
        # prompt == prefix: the fork must leave ≥ 1 token to process (the
        # last prompt token's logits seed decoding), still bit-identical
        _fork_vs_recompute(
            CFG, lambda: ServeEngine(CFG, params, **ENG_KW),
            [list(PREFIX), PREFIX + [4]], PREFIX)

    def test_non_kv_family_recomputes_with_warning(self):
        # rwkv6 carries recurrent per-slot state: forking KV rows alone
        # would be wrong, so the scheduler must fall back to recompute
        # (correct tokens, no fork) and say so once
        cfg = configs.get_config("rwkv6-1.6b", "smoke").replace(
            dtype="float32", param_dtype="float32")
        fam = mapi.get_family(cfg.family)
        p = fam.init(jax.random.PRNGKey(0), cfg)
        prompts = [PREFIX + [5, 6], PREFIX + [11]]
        ref, _ = _recompute_tokens(
            cfg, lambda: ServeEngine(cfg, p, **ENG_KW), prompts)
        eng = ServeEngine(cfg, p, **ENG_KW)
        sched = Scheduler(eng)
        sched.register_prefix("sys", PREFIX)
        assert not sched.pool.fork_capable
        for i, pr in enumerate(prompts):
            sched.submit(list(pr), max_new_tokens=5, prefix="sys", rid=i)
        with pytest.warns(RuntimeWarning, match="recomputed, not forked"):
            done = {g.rid: g.tokens for g in sched.run()}
        assert done == ref
        assert sched.stats["forks"] == 0
        assert sched.stats["prefix_recompute"] == len(prompts)


class TestPrefixPool:
    def test_eviction_while_fork_live(self, params):
        # two prefixes, capacity 1: admitting a "b" request evicts the
        # pooled "a" entry while an "a" fork is mid-decode — the live fork
        # owns copies, so its tokens stay identical to recompute, and the
        # next "a" request re-prefills the pool
        other = [9, 9, 2, 2]
        prompts = [PREFIX + [5, 6], other + [3], PREFIX + [1]]
        ref, _ = _recompute_tokens(
            CFG, lambda: ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                                     prefill_chunk=4), prompts, n_new=6)
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng, prefix_capacity=1)
        sched.register_prefix("a", PREFIX)
        sched.register_prefix("b", other)
        for i, (p, key) in enumerate(zip(prompts, ["a", "b", "a"])):
            sched.submit(list(p), max_new_tokens=6, prefix=key, rid=i)
        done = {g.rid: g.tokens for g in _quiet_run(sched)}
        assert done == ref
        assert sched.pool.evictions >= 2       # a evicted by b, b by a
        # "a" was prefilled twice (initial + after eviction), "b" once
        assert sched.pool.prefill_steps > 0
        assert sched.stats["forks"] == 3

    def test_explicit_evict_keeps_live_fork_decoding(self, params):
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng)
        sched.register_prefix("sys", PREFIX)
        h = sched.submit(PREFIX + [5, 6], max_new_tokens=6, prefix="sys")
        stream = h.stream()
        first = next(stream)                   # fork done, decoding started
        sched.pool.evict("sys")                # yank the pooled entry
        assert "sys" not in sched.pool.resident
        rest = list(stream)
        ref, _ = _recompute_tokens(
            CFG, lambda: ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                                     prefill_chunk=4),
            [PREFIX + [5, 6]], n_new=6)
        assert [first] + rest == ref[0]

    def test_register_validates(self, params):
        eng = ServeEngine(CFG, params, **ENG_KW)
        sched = Scheduler(eng)
        with pytest.raises(ValueError, match="empty"):
            sched.register_prefix("x", [])
        with pytest.raises(ValueError, match="KV budget"):
            sched.register_prefix("x", list(range(200)) * 2)
        with pytest.raises(KeyError, match="not registered"):
            sched.submit([1, 2], prefix="nope")

    def test_prompt_must_start_with_prefix(self, params):
        eng = ServeEngine(CFG, params, **ENG_KW)
        sched = Scheduler(eng)
        sched.register_prefix("sys", PREFIX)
        with pytest.raises(ValueError, match="does not start with prefix"):
            sched.submit([1, 2, 3], prefix="sys")


class TestPriorityAdmission:
    def test_strict_priority_order(self, params):
        # aging=0: pure priority. One slot, three requests — the
        # high-priority one seats first despite being submitted last.
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng, aging=0.0)
        lo = [sched.submit([1, 2, i], max_new_tokens=3, priority=0.0)
              for i in range(2)]
        hi = sched.submit([3, 4, 5], max_new_tokens=3, priority=5.0)
        _quiet_run(sched)
        assert hi.generation.queue_steps == 0
        assert all(h.generation.queue_steps > 0 for h in lo)
        # FIFO among equals
        assert (lo[0].generation.queue_steps
                < lo[1].generation.queue_steps)

    def test_aging_prevents_starvation(self, params):
        # a steady stream of high-priority arrivals; with aging the old
        # low-priority request must still seat before the *last* of them
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng, aging=1.0)   # 1 step of waiting = 1 priority
        lo = sched.submit([1, 2], max_new_tokens=2, priority=0.0)
        his = [sched.submit([3, 3 + i], max_new_tokens=2, priority=3.0,
                            at=float(i)) for i in range(8)]
        _quiet_run(sched)
        assert lo.done and all(h.done for h in his)
        last_hi = his[-1]
        assert (lo.generation.t_admit < last_hi.generation.t_admit), \
            "aged low-priority request starved behind fresh high-priority"

    def test_all_requests_complete_under_load(self, params):
        eng = ServeEngine(CFG, params, **ENG_KW)
        sched = Scheduler(eng)
        hs = [sched.submit([1 + i, 2, 3], max_new_tokens=4,
                           priority=float(i % 3)) for i in range(9)]
        done = _quiet_run(sched)
        assert len(done) == 9
        assert all(h.done for h in hs)


class TestStreamLifecycle:
    def test_stream_yields_incrementally(self, params):
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng)
        h = sched.submit([1, 2, 3, 4], max_new_tokens=5)
        seen = []
        for tok in h.stream():
            seen.append(tok)
            assert h.tokens == seen      # no lookahead past the yield
        assert h.done and len(seen) == 5
        ref, _ = _recompute_tokens(
            CFG, lambda: ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                                     prefill_chunk=4), [[1, 2, 3, 4]])
        assert seen == ref[0]

    def test_latency_stamps_ordered(self, params):
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng)
        early = sched.submit([1, 2, 3], max_new_tokens=3)
        late = sched.submit([4, 5, 6], max_new_tokens=3)
        _quiet_run(sched)
        for h in (early, late):
            g = h.generation
            assert g.t_submit > 0
            assert g.t_submit <= g.t_admit <= g.t_first_token <= g.t_done
        assert early.generation.queue_steps == 0
        assert late.generation.queue_steps > 0   # waited for the one slot

    def test_result_drives_to_completion(self, params):
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng)
        h = sched.submit([1, 2, 3], max_new_tokens=4, at=25.0)  # future
        g = h.result()          # fast-forwards the virtual clock
        assert g.done and len(g.tokens) == 4

    def test_virtual_arrivals_release_in_order(self, params):
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng)
        a = sched.submit([1, 2], max_new_tokens=2, at=0.0)
        b = sched.submit([3, 4], max_new_tokens=2, at=50.0)
        _quiet_run(sched)
        assert a.done and b.done
        assert a.generation.t_admit <= b.generation.t_admit


class TestExpiryAccounting:
    def test_never_stepped_slot_counts_as_queued(self, params):
        # B=1; request 0 takes exactly 3 steps (1 prefill chunk + 2 decode)
        # so the mid-wave refill at the end of step 3 seats request 1 —
        # which has executed nothing when max_steps=3 expires. It must be
        # reported as QUEUED (and un-admitted), not as a live partial.
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        for i in range(2):
            eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=3,
                               rid=i))
        with pytest.warns(RuntimeWarning,
                          match=r"0 live slot\(s\) and 1 queued"):
            done = eng.run(max_steps=3)
        assert [g.rid for g in done] == [0]
        assert len(done[0].tokens) == 3
        assert all(s is None for s in eng._slots)
        assert [r.rid for r in eng._queue] == [1]

    def test_resumption_after_expiry_is_exact(self, params):
        ref_eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                              prefill_chunk=4)
        for i in range(2):
            ref_eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=3,
                                   rid=i))
        ref = {g.rid: g.tokens for g in _quiet_run(ref_eng)}
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        for i in range(2):
            eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=3,
                               rid=i))
        out = {g.rid: g.tokens for g in _quiet_run(eng, max_steps=3)}
        out.update({g.rid: g.tokens for g in _quiet_run(eng)})
        assert out == ref

    def test_queue_steps_of_unadmitted_request_stays_exact(self, params):
        # the un-admitted request re-enters through _fill_slots later; its
        # queue_steps must measure from the original submit step
        eng = ServeEngine(CFG, params, batch_slots=1, kv_len=64,
                          prefill_chunk=4)
        for i in range(2):
            eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=3,
                               rid=i))
        _quiet_run(eng, max_steps=3)
        done = _quiet_run(eng)
        (g,) = done
        assert g.rid == 1 and g.queue_steps == 3


# (arrival step, prompt body, max_new_tokens, shares PREFIX): arrivals
# outpace the 3 slots, so requests queue and slots turn over mid-wave
ARRIVALS = [(0.0, [5, 6], 6, True), (0.0, [11], 4, True),
            (1.0, [3, 1, 4, 1, 5], 5, False), (1.5, [1, 2, 3], 8, True),
            (2.0, list(range(10, 19)), 4, True), (3.0, [9, 2, 6], 6, False),
            (4.5, [7], 5, True), (5.0, [2, 7, 1, 8], 7, True),
            (6.0, [4, 4], 6, False), (7.5, [6, 1, 3], 5, True)]


class TestTrafficReplay:
    @staticmethod
    def _replay(params, use_prefix=True, fault=None):
        """Serve ARRIVALS through a fresh engine's scheduler; returns what
        a second replay must reproduce, and each request's outcome."""
        eng = ServeEngine(CFG, params, batch_slots=3, kv_len=64,
                          prefill_chunk=4)
        sched = Scheduler(eng)
        sched.register_prefix("sys", PREFIX)
        handles = [sched.submit(
            (PREFIX if shared else []) + body, max_new_tokens=n, at=at,
            rid=rid, prefix="sys" if shared and use_prefix else None)
            for rid, (at, body, n, shared) in enumerate(ARRIVALS)]
        if fault is not None:
            faults.inject_nan_logits(eng, *fault)
        _quiet_run(sched)
        gens = [h.generation for h in handles]
        outcomes = ["failed" if g.failed else "done" if g.done else "live"
                    for g in gens]
        return dict(tokens=[list(g.tokens) for g in gens], outcomes=outcomes,
                    steps=eng.steps_total,
                    prefill=eng.prefill_slot_steps + sched.pool.prefill_steps,
                    forks=sched.stats["forks"],
                    queue=[q.waiting for q in sched.queue_trace])

    def test_replay_deterministic_and_complete(self, params):
        r1, r2 = self._replay(params), self._replay(params)
        assert r1 == r2
        assert r1["outcomes"] == ["done"] * len(ARRIVALS)
        assert max(r1["queue"]) >= 1         # load actually queued

    def test_reuse_vs_no_reuse(self, params):
        r = self._replay(params)
        rn = self._replay(params, use_prefix=False)
        assert r["tokens"] == rn["tokens"]
        assert r["prefill"] < rn["prefill"]
        assert r["forks"] > 0 and rn["forks"] == 0

    def test_faulted_replay_deterministic(self, params):
        # NaN logits on slot 1 for device steps 4..9
        r1 = self._replay(params, fault=(1, 4, 6))
        r2 = self._replay(params, fault=(1, 4, 6))
        assert r1 == r2
        assert "failed" in r1["outcomes"]
        assert set(r1["outcomes"]) == {"done", "failed"}
