"""Serving fault-tolerance tests: every recovery path of the robustness
layer exercised against real injected faults (``serve.faults``) —
checkpoint integrity rejection by tensor name, slot quarantine with
bit-identical survivors, deadlines, the run() watchdog, step retry, the
dense degraded-mode fallback, and admission faults."""
import warnings

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import IntegrityError, build_plan, verify_packed_tree
from repro.models import api as mapi
from repro.serve import faults
from repro.serve.engine import Request, ServeEngine

CFG = configs.get_config("paper-100m", "smoke").replace(dtype="float32",
                                                        param_dtype="float32")
FMT = "babsmax32:n4"        # 4-bit nibble-packed serving checkpoint
FMT_8BIT = "babsmax32:n5"   # 32-point codebook → uint8 codes (range faults)
ENG_KW = dict(batch_slots=3, kv_len=64, prefill_chunk=4)


@pytest.fixture(scope="module")
def ckpt():
    fam = mapi.get_family(CFG.family)
    params = fam.init(jax.random.PRNGKey(0), CFG)
    plan = build_plan(params, FMT)
    return plan, plan.quantise(params), params


def _engine(plan, q, **kw):
    return ServeEngine.from_quantised(CFG, q, plan, **{**ENG_KW, **kw})


def _quiet_run(eng, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return eng.run(**kw)


def _reqs(n, max_new=6):
    return [Request(prompt=[1 + r, 2, 3, 4], max_new_tokens=max_new, rid=r)
            for r in range(n)]


def _submit_all(eng, reqs):
    for r in reqs:
        eng.submit(Request(prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens, rid=r.rid,
                           deadline_steps=r.deadline_steps))


class TestIntegrityValidation:
    def test_clean_checkpoint_loads_and_counts_leaves(self, ckpt):
        plan, q, params = ckpt
        eng = _engine(plan, q)
        n = verify_packed_tree(eng.params)
        assert n >= 1  # the packed tree really was validated leaf by leaf

    def test_corrupt_scales_rejected_naming_tensor(self, ckpt):
        plan, q, params = ckpt
        tensor = faults.packed_paths(q)[0]
        bad = faults.corrupt_scales(q, tensor)
        with pytest.raises(IntegrityError) as ei:
            _engine(plan, bad)
        assert tensor in str(ei.value)
        assert "scales" in str(ei.value)

    def test_corrupt_codes_rejected_naming_tensor(self, ckpt):
        # byte 0xFF is out of range for the 32-point codebook stored uint8
        # (4-bit nibble-packed tensors can't see range faults — both
        # nibbles of any byte are valid <16 codes — hence the 8-bit plan)
        plan, q, params = ckpt
        plan8 = build_plan(params, FMT_8BIT)
        q8 = plan8.quantise(params)
        tensor = faults.packed_paths(q8)[0]
        with pytest.raises(IntegrityError) as ei:
            _engine(plan8, faults.corrupt_codes(q8, tensor))
        assert tensor in str(ei.value)
        assert "out of codebook range" in str(ei.value)

    def test_corrupt_layout_rejected(self, ckpt):
        plan, q, params = ckpt
        layouts = mapi.get_family(CFG.family).pack_layouts(CFG)
        packed = plan.pack_quantised(q, layouts)
        tensor = faults.packed_paths(packed)[0]
        with pytest.raises(IntegrityError) as ei:
            verify_packed_tree(faults.corrupt_layout(packed, tensor))
        assert tensor in str(ei.value)

    def test_validate_false_escape_hatch(self, ckpt):
        plan, q, params = ckpt
        tensor = faults.packed_paths(q)[0]
        bad = faults.corrupt_scales(q, tensor)
        eng = ServeEngine.from_quantised(CFG, bad, plan, validate=False,
                                         **ENG_KW)
        assert eng._has_packed()  # loaded without the integrity pass

    def test_unknown_target_lists_paths(self, ckpt):
        plan, q, params = ckpt
        with pytest.raises(KeyError) as ei:
            faults.corrupt_scales(q, "['nonexistent']")
        # the error lists the valid targets (str(KeyError) re-escapes
        # quotes, so check for the bare tensor names)
        assert "embed" in str(ei.value) and "targets" in str(ei.value)


class TestSubmitValidation:
    def test_empty_prompt_rejected(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(Request(prompt=[], max_new_tokens=4))

    def test_nonpositive_max_new_rejected(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        for bad in (0, -3):
            with pytest.raises(ValueError, match="max_new_tokens"):
                eng.submit(Request(prompt=[1, 2], max_new_tokens=bad))

    def test_bad_deadline_rejected(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        with pytest.raises(ValueError, match="deadline_steps"):
            eng.submit(Request(prompt=[1, 2], max_new_tokens=4,
                               deadline_steps=0))

    def test_duplicate_rid_warns(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        eng.submit(Request(prompt=[1, 2], max_new_tokens=2, rid=7))
        with pytest.warns(RuntimeWarning, match="rid=7"):
            eng.submit(Request(prompt=[3, 4], max_new_tokens=2, rid=7))

    def test_distinct_rids_do_not_warn(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            eng.submit(Request(prompt=[1, 2], max_new_tokens=2, rid=1))
            eng.submit(Request(prompt=[3, 4], max_new_tokens=2, rid=2))


class TestSlotQuarantine:
    def test_nan_quarantines_only_offending_slot(self, ckpt):
        plan, q, _ = ckpt
        eng_ref, eng_hit = _engine(plan, q), _engine(plan, q)
        _submit_all(eng_ref, _reqs(3))
        _submit_all(eng_hit, _reqs(3))
        ctr = faults.inject_nan_logits(eng_hit, slot=0, at_step=2)
        ref = {g.rid: g for g in _quiet_run(eng_ref)}
        with pytest.warns(RuntimeWarning, match="quarantined slot 0"):
            hit = {g.rid: g for g in eng_hit.run()}
        assert ctr["injected"] == 1
        assert len(hit) == len(ref) == 3  # nothing silently lost
        failed = [g for g in hit.values() if g.failed]
        assert len(failed) == 1 and failed[0].rid == 0
        assert not failed[0].done
        assert "non-finite logits" in failed[0].fail_reason
        # survivors bit-identical to the undisturbed engine
        for g in hit.values():
            if g.failed:
                assert g.tokens == ref[g.rid].tokens[:len(g.tokens)]
            else:
                assert g.done and g.tokens == ref[g.rid].tokens

    def test_slot_reused_after_quarantine_is_clean(self, ckpt):
        # the quarantined slot's poisoned state must be wiped by the reset
        # protocol: a request admitted into it decodes exactly what it
        # would on a fresh engine
        plan, q, _ = ckpt
        eng = _engine(plan, q, batch_slots=1)
        eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=8, rid=0))
        eng.submit(Request(prompt=[8, 9], max_new_tokens=4, rid=1))
        faults.inject_nan_logits(eng, slot=0, at_step=1)
        gens = {g.rid: g for g in _quiet_run(eng)}
        assert gens[0].failed and not gens[1].failed
        fresh = _engine(plan, q, batch_slots=1)
        fresh.submit(Request(prompt=[8, 9], max_new_tokens=4, rid=1))
        assert gens[1].tokens == fresh.run()[0].tokens

    def test_deadline_quarantines_runaway_request(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=30,
                           deadline_steps=3, rid=0))
        eng.submit(Request(prompt=[4, 5, 6], max_new_tokens=4, rid=1))
        gens = {g.rid: g for g in _quiet_run(eng)}
        assert gens[0].failed and "deadline_steps=3" in gens[0].fail_reason
        assert len(gens[0].tokens) < 30
        assert gens[1].done and not gens[1].failed

    def test_no_deadline_by_default(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=6, rid=0))
        (g,) = eng.run()
        assert g.done and not g.failed and len(g.tokens) == 6


class TestRunExpiryUnderFaults:
    def test_resume_after_quarantine_is_bit_identical(self, ckpt):
        # satellite: max_steps expiry mid-wave + a quarantine, then
        # resume — surviving slots continue with tokens identical to an
        # engine that was never interrupted or faulted
        plan, q, _ = ckpt
        eng_ref, eng_hit = _engine(plan, q), _engine(plan, q)
        reqs = _reqs(3, max_new=8)
        _submit_all(eng_ref, reqs)
        _submit_all(eng_hit, reqs)
        faults.inject_nan_logits(eng_hit, slot=0, at_step=2)
        ref = {g.rid: g for g in _quiet_run(eng_ref)}
        first = _quiet_run(eng_hit, max_steps=3)   # expires mid-wave
        assert any(g.failed for g in first)        # quarantine happened
        assert any(not g.done and not g.failed for g in first)  # partials
        rest = _quiet_run(eng_hit)                 # resume survivors
        final = {g.rid: g for g in rest if g.done}
        assert set(final) == {1, 2}
        for rid, g in final.items():
            assert g.tokens == ref[rid].tokens


class TestWatchdog:
    def test_deadline_s_returns_resumable_partials(self, ckpt):
        plan, q, _ = ckpt
        eng_ref, eng_hit = _engine(plan, q), _engine(plan, q)
        reqs = _reqs(2, max_new=6)
        _submit_all(eng_ref, reqs)
        _submit_all(eng_hit, reqs)
        ref = {g.rid: g.tokens for g in _quiet_run(eng_ref)}
        orig_step = eng_hit._step
        faults.inject_slow_steps(eng_hit, range(100), delay_s=0.2)
        with pytest.warns(RuntimeWarning, match="watchdog"):
            partial = eng_hit.run(deadline_s=0.3)
        assert partial and all(not g.done for g in partial)
        # un-stall (drop the injector) and resume: the wave completes
        # bit-identically to the never-interrupted engine
        eng_hit._step = orig_step
        done = {g.rid: g.tokens for g in _quiet_run(eng_hit)}
        assert done == ref


class TestStepRetryAndFallback:
    def test_retry_absorbs_transient_failure(self, ckpt):
        plan, q, _ = ckpt
        eng_ref = _engine(plan, q)
        eng_hit = _engine(plan, q, step_retries=3)
        for e in (eng_ref, eng_hit):
            e.submit(Request(prompt=[5, 6, 7], max_new_tokens=6, rid=0))
        ctr = faults.inject_step_failures(eng_hit, {1})
        a = eng_ref.run()[0].tokens
        b = eng_hit.run()[0].tokens
        assert ctr["raised"] == 1
        assert not eng_hit.degraded       # retry succeeded, no fallback
        assert a == b

    def test_persistent_failure_degrades_to_dense(self, ckpt):
        plan, q, _ = ckpt
        eng_ref = _engine(plan, q)
        eng_hit = _engine(plan, q)
        for e in (eng_ref, eng_hit):
            e.submit(Request(prompt=[5, 6, 7], max_new_tokens=6, rid=0))
        faults.inject_step_failures(eng_hit, {1})
        a = eng_ref.run()[0].tokens
        with pytest.warns(RuntimeWarning, match="degraded mode"):
            b = eng_hit.run()[0].tokens
        assert eng_hit.degraded
        assert not eng_hit._has_packed()  # every leaf dequantised
        assert a == b                     # dequantise is bit-faithful

    def test_fallback_disabled_propagates(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q, dense_fallback=False)
        eng.submit(Request(prompt=[1, 2], max_new_tokens=4, rid=0))
        faults.inject_step_failures(eng, {0})
        with pytest.raises(RuntimeError, match="injected"):
            eng.run()

    def test_manual_degrade_is_idempotent(self, ckpt):
        plan, q, _ = ckpt
        eng_ref, eng_hit = _engine(plan, q), _engine(plan, q)
        with pytest.warns(RuntimeWarning, match="degraded mode"):
            eng_hit.degrade_to_dense(reason="test kill-switch")
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            eng_hit.degrade_to_dense()    # second call: silent no-op
        for e in (eng_ref, eng_hit):
            e.submit(Request(prompt=[7, 8, 9], max_new_tokens=6, rid=0))
        assert eng_ref.run()[0].tokens == eng_hit.run()[0].tokens

    def test_bad_step_retries_rejected(self, ckpt):
        plan, q, _ = ckpt
        with pytest.raises(ValueError, match="step_retries"):
            _engine(plan, q, step_retries=0)


class TestAdmissionFaults:
    def test_drop_admissions_loses_only_target(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        _submit_all(eng, _reqs(3))
        dropped = faults.drop_admissions(eng, {1})
        gens = {g.rid for g in eng.run()}
        assert gens == {0, 2}
        assert [r.rid for r in dropped] == [1]

    def test_duplicate_admissions_run_identically(self, ckpt):
        plan, q, _ = ckpt
        eng = _engine(plan, q)
        eng.submit(Request(prompt=[3, 4, 5], max_new_tokens=4, rid=0))
        state = faults.duplicate_admissions(eng, {0})
        gens = eng.run()
        assert state["duplicated"] == 1
        assert len(gens) == 2
        assert gens[0].tokens == gens[1].tokens  # greedy → same stream
