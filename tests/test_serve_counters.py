"""The engine's own measurement: the counters ``ServeEngine.counters()``
returns, checked against what the steps really computed and what the
requests really got, and the ``serve.*`` spans ``step_once`` writes into
a profiler trace, each step's ending with the counters."""
import warnings

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import build_plan
from repro.models import api as mapi
from repro.serve import faults
from repro.serve.engine import Request, ServeEngine

CFG = configs.get_config("paper-100m", "smoke").replace(dtype="float32",
                                                        param_dtype="float32")
ENG_KW = dict(batch_slots=3, kv_len=64, prefill_chunk=4)
# the host phases of a step, in order, inside its serve.step span
PHASES = ["admit", "assemble", "dispatch", "device_wait", "logits_to_host",
          "sample", "refill"]
# ragged prompts (1 to 3 chunks, one shorter than a chunk) and outputs; every
# request decodes at least once, as in the benchmark's mixes (the harness's
# per-slot accounting cannot see a request that ends on its prefill step)
PROMPTS = [[1 + r] + list(range(2, 2 + n)) for r, n in
           enumerate([2, 6, 9, 3, 11])]
MAX_NEW = [2, 3, 7, 2, 4]


@pytest.fixture(scope="module")
def ckpt():
    fam = mapi.get_family(CFG.family)
    params = fam.init(jax.random.PRNGKey(0), CFG)
    plan = build_plan(params, "babsmax32:n4")
    return plan, plan.quantise(params)


def _engine(ckpt, max_new=MAX_NEW):
    plan, q = ckpt
    eng = ServeEngine.from_quantised(CFG, q, plan, **ENG_KW)
    for rid, (p, n) in enumerate(zip(PROMPTS, max_new)):
        eng.submit(Request(prompt=list(p), max_new_tokens=n, rid=rid))
    return eng


def _record_shapes(eng):
    """Wrap the jitted step to record each call's (tokens, logits) shapes."""
    inner, shapes = eng._step, []

    def wrapped(p, s, b):
        logits, state = inner(p, s, b)
        shapes.append((b["tokens"].shape, logits.shape, logits.dtype))
        return logits, state

    eng._step = wrapped
    return shapes


@pytest.fixture(scope="module")
def served(ckpt):
    """The ragged requests served to the end, one ``step_once`` at a time,
    with the benchmark's per-step accounting beside the engine's."""
    from chipbench.harness import Slots
    eng = _engine(ckpt)
    shapes = _record_shapes(eng)
    slots, steps, finished = Slots(eng), [], []
    while eng.step_once(finished):
        steps.append(slots.after_step(0.0, 0.0))
    return eng, shapes, steps, finished


def test_computed_tokens_and_logits_bytes_match_the_steps(served):
    """Greedy requests only: each step copies a (B,) int32 token and a (B,)
    bool finiteness bit to the host, never a logits row."""
    eng, shapes, _, _ = served
    assert eng.tokens_computed == sum(B * T for (B, T), _, _ in shapes)
    assert eng.logits_rows_to_host == 0
    assert eng.logits_host_bytes == sum(
        lshape[0] * (np.dtype(np.int32).itemsize + np.dtype(bool).itemsize)
        for _, lshape, _ in shapes)
    assert eng.logits_host_bytes == sum(B * 5 for (B, T), _, _ in shapes)


def test_valid_and_emitted_tokens_match_the_requests(served):
    eng, _, steps, finished = served
    assert len(finished) == len(PROMPTS)
    assert all(g.done and not g.failed for g in finished)
    by_rid = {g.rid: g for g in finished}
    want = sum(len(PROMPTS[r]) + len(g.tokens) - 1 for r, g in by_rid.items())
    assert eng.tokens_valid == want
    assert eng.tokens_valid == sum(s["valid"] for s in steps)
    assert eng.tokens_emitted == sum(len(g.tokens) for g in finished) \
        == sum(MAX_NEW)
    assert eng.tokens_valid < eng.tokens_computed


def test_counters_snapshot_agrees_with_the_attributes(served):
    eng, shapes, _, _ = served
    c = eng.counters()
    assert c["steps_total"] == eng.steps_total == len(shapes)
    assert c["prefill_steps"] == eng.prefill_steps
    assert c["prefill_slot_steps"] == eng.prefill_slot_steps
    assert c["prefill_steps"] == sum(T > 1 for (_, T), _, _ in shapes)
    for k in ("tokens_valid", "tokens_computed", "tokens_emitted",
              "logits_host_bytes", "logits_rows_to_host",
              "recurrent_state_bytes"):
        assert c[k] == getattr(eng, k)
    assert c["recurrent_state_bytes"] == 0      # a transformer has none
    assert all(isinstance(v, int) for v in c.values())


def test_a_quarantined_request_counts_what_it_was_served(ckpt):
    """A slot poisoned mid-run: its step still counts the rows it computed
    and the tokens and bits it copied, only the tokens really emitted count
    as emitted, and quarantine needs no logits row on the host."""
    eng = _engine(ckpt)
    shapes = _record_shapes(eng)
    ctr = faults.inject_nan_logits(eng, slot=2, at_step=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        out = eng.run()
    assert ctr["injected"] == 1 and sum(g.failed for g in out) == 1
    assert eng.tokens_emitted == sum(len(g.tokens) for g in out) \
        < sum(MAX_NEW)
    assert eng.tokens_computed == sum(B * T for (B, T), _, _ in shapes)
    assert eng.logits_host_bytes == sum(B * 5 for (B, T), _, _ in shapes)
    assert eng.logits_rows_to_host == 0
    assert eng.steps_total == len(shapes)


@pytest.fixture(scope="module")
def traced(ckpt, tmp_path_factory):
    """Two ``step_once`` calls under the profiler, read back with the
    benchmark's reader of the program's spans: (engine, spans, the
    counters after each step)."""
    from chipbench import program_trace, trace
    eng = _engine(ckpt, max_new=[1] + MAX_NEW[1:])
    finished, after = [], []
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        for _ in range(2):
            eng.step_once(finished)
            after.append(eng.counters())
    finally:
        jax.profiler.stop_trace()
    return eng, program_trace.load(trace.find_xplane(out))["spans"], after


def test_trace_shows_the_step_phases_in_order(traced):
    """Each ``serve.step`` encloses one span per phase, in order, and each
    ``serve.seat`` sits inside its admission pass and names the request
    and slot."""
    _, spans, _ = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == 2
    seats = []
    for _, s0, d0, attrs in steps:
        inside = [s for s in spans if s[0] != "serve.step"
                  and s0 <= s[1] and s[1] + s[2] <= s0 + d0]
        top = [s for s in inside if s[0] != "serve.seat"]
        assert [s[0] for s in top] == [f"serve.{p}" for p in PHASES]
        assert [s[1] for s in top] == sorted(s[1] for s in top)
        assert attrs["T"] in (1, ENG_KW["prefill_chunk"])
        assert 0 < attrs["live"] <= ENG_KW["batch_slots"]
        for seat in (s for s in inside if s[0] == "serve.seat"):
            parent = [s for s in top if s[1] <= seat[1]
                      and seat[1] + seat[2] <= s[1] + s[2]]
            assert [s[0] for s in parent] in (["serve.admit"],
                                              ["serve.refill"])
            seats.append(seat)
    # the first step seats three requests, and its refill seats a fourth
    # in the slot that the one-token request freed on its prefill step
    assert [(s[3]["rid"], s[3]["slot"]) for s in seats] == [
        (0, 0), (1, 1), (2, 2), (3, 0)]


def test_each_step_span_ends_with_the_counters(traced):
    """A ``serve.step`` span carries ``counters()`` as the step left them,
    so a trace of the last steps gives the counts of every step before."""
    eng, spans, after = traced
    steps = [s[3] for s in spans if s[0] == "serve.step"]
    got = [{k: int(a[k]) for k in c} for a, c in zip(steps, after)]
    assert got == after
    assert got[-1] == eng.counters()
    first = got[0]
    assert first["steps_total"] == 1
    assert first["tokens_computed"] == ENG_KW["batch_slots"] * steps[0]["T"]
    assert first["logits_host_bytes"] == ENG_KW["batch_slots"] * 5
    assert first["logits_rows_to_host"] == 0
    assert 0 < first["tokens_valid"] <= first["tokens_computed"]
