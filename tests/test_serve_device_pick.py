"""The greedy pick on the device: every token a slot emits is the argmax of
its last valid logits row, taken where the step ran, and the host copies
only (B,) tokens and finiteness bits — the (B, V) rows only on a step
where a slot that emits samples at ``temperature > 0``, which then draws
from them with the same numpy stream as before."""
import warnings

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import api as mapi
from repro.serve import faults
from repro.serve.engine import Request, ServeEngine

ENG_KW = dict(batch_slots=3, kv_len=64, prefill_chunk=4)
# ragged prompts (a few tokens to three chunks) and outputs, more requests
# than slots, so slots turn over mid-wave
PROMPTS = [[1 + r] + list(range(2, 2 + n)) for r, n in
           enumerate([2, 6, 9, 3, 11])]
MAX_NEW = [2, 3, 7, 2, 4]


def _cfg(arch):
    return configs.get_config(arch, "smoke").replace(dtype="float32",
                                                     param_dtype="float32")


@pytest.fixture(scope="module", params=["paper-100m", "rwkv6-1.6b"])
def model(request):
    cfg = _cfg(request.param)
    return cfg, mapi.get_family(cfg.family).init(jax.random.PRNGKey(0), cfg)


def _engine(model, temperatures=None):
    cfg, params = model
    eng = ServeEngine(cfg, params, **ENG_KW)
    temps = temperatures or [0.0] * len(PROMPTS)
    for rid, (p, n, t) in enumerate(zip(PROMPTS, MAX_NEW, temps)):
        eng.submit(Request(prompt=list(p), max_new_tokens=n, rid=rid,
                           temperature=t))
    return eng


def _record(eng):
    """Wrap the jitted step to record, for each call, its logits on the
    host, the batch's ``t_valid`` and each slot's generation with the
    number of tokens it had when the step ran."""
    inner, steps = eng._step, []

    def wrapped(p, s, b):
        logits, state = inner(p, s, b)
        steps.append(dict(
            logits=np.asarray(logits), t_valid=np.asarray(b["t_valid"]),
            slots=[(g, len(g.tokens)) if g is not None else None
                   for g in eng._slots]))
        return logits, state

    eng._step = wrapped
    return steps


def _emitted(steps):
    """(step index, generation, token index, the slot's last valid logits
    row) for every token the recorded steps emitted: a step emitted a
    slot's token if the generation holds more tokens when it next runs, or
    at the end."""
    out = []
    for k, st in enumerate(steps):
        for i, slot in enumerate(st["slots"]):
            if slot is None:
                continue
            g, n = slot
            later = [s[1] for nxt in steps[k + 1:] for s in nxt["slots"]
                     if s is not None and s[0] is g]
            if (later[0] if later else len(g.tokens)) > n:
                out.append((k, g, n, st["logits"][i, st["t_valid"][i] - 1]))
    return out


def _drawn(row, temperature, rid, index):
    """What numpy sampling at ``temperature`` draws from a host row."""
    z = row / temperature
    p = np.exp(z - z.max())
    p /= p.sum()
    rng = np.random.default_rng((rid & 0xFFFFFFFF, index))
    return int(rng.choice(len(p), p=p))


def test_greedy_tokens_are_the_argmax_of_the_full_rows(model):
    eng = _engine(model)
    steps = _record(eng)
    out = eng.run()
    assert len(out) == len(PROMPTS) and all(g.done for g in out)
    emitted = _emitted(steps)
    assert len(emitted) == sum(MAX_NEW) == eng.tokens_emitted
    for _, g, n, row in emitted:
        assert g.tokens[n] == int(np.argmax(row))
    assert eng.logits_rows_to_host == 0
    assert eng.logits_host_bytes == len(steps) * ENG_KW["batch_slots"] * 5


def test_sampled_slots_draw_from_the_copied_rows(model):
    """Greedy and ``temperature > 0`` requests in one batch: the sampled
    tokens are numpy's draws from the rows copied, and the rows are copied
    on exactly the steps where a sampled slot emitted."""
    temps = [0.0, 0.9, 0.0, 1.3, 0.7]
    eng = _engine(model, temps)
    steps = _record(eng)
    out = eng.run()
    assert len(out) == len(PROMPTS) and all(g.done for g in out)
    emitted = _emitted(steps)
    assert len(emitted) == sum(MAX_NEW)
    for _, g, n, row in emitted:
        t = temps[g.rid]
        want = _drawn(row, t, g.rid, n) if t > 0 else int(np.argmax(row))
        assert g.tokens[n] == want
    sampled_steps = {k for k, g, _, _ in emitted if temps[g.rid] > 0}
    assert 0 < len(sampled_steps) < len(steps)
    assert eng.logits_rows_to_host == len(sampled_steps)
    cfg, _ = model
    B = ENG_KW["batch_slots"]
    assert eng.logits_host_bytes == len(steps) * B * 5 \
        + len(sampled_steps) * B * cfg.vocab * 4


def test_nan_logits_quarantine_only_the_poisoned_slot(model):
    """``inject_nan_logits`` poisons one slot's step output: the device's
    finiteness bit quarantines that slot alone, with no row copied, and
    every other request serves what a clean run serves."""
    clean = {g.rid: g.tokens for g in _engine(model).run()}
    eng = _engine(model)
    steps = _record(eng)
    ctr = faults.inject_nan_logits(eng, slot=1, at_step=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        out = eng.run()
    assert ctr["injected"] == 1
    failed = [g for g in out if g.failed]
    assert len(failed) == 1
    assert failed[0] is steps[3]["slots"][1][0]
    assert "non-finite" in failed[0].fail_reason
    assert all(g.done and g.tokens == clean[g.rid]
               for g in out if not g.failed)
    assert eng.logits_rows_to_host == 0


@pytest.mark.parametrize("arch", ["paper-100m", "gemma3-1b",
                                  "qwen2-moe-a2.7b", "internvl2-26b",
                                  "rwkv6-1.6b", "zamba2-2.7b",
                                  "whisper-large-v3"])
def test_every_family_steps_to_vocab_wide_f32_logits(arch):
    """``_pick`` is compiled at construction for (B, T, vocab) float32
    logits: every family's step returns exactly that, at both T."""
    cfg = configs.get_config(arch, "smoke")
    fam = mapi.get_family(cfg.family)
    eng = ServeEngine(cfg, fam.init(jax.random.PRNGKey(0), cfg), **ENG_KW)
    B = ENG_KW["batch_slots"]
    for T in (1, ENG_KW["prefill_chunk"]):
        batch = {"tokens": jax.ShapeDtypeStruct((B, T), np.int32),
                 "t_valid": jax.ShapeDtypeStruct((B,), np.int32)}
        logits, _ = jax.eval_shape(eng._step, eng.params, eng._state, batch)
        assert (logits.shape, logits.dtype) == ((B, T, cfg.vocab),
                                                np.float32)


def test_no_step_compiles_the_pick(model):
    """With the step variants compiled as the benchmark warms them, a whole
    ragged run, greedy and sampled, compiles nothing more."""
    from jax._src import monitoring
    from chipbench import harness
    eng = _engine(model, [0.0, 0.9, 0.0, 1.3, 0.7])
    harness.warm(eng, [], on_tpu=False)
    compiles = []

    def count(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    monitoring.register_event_duration_secs_listener(count)
    try:
        out = eng.run()
    finally:
        monitoring.unregister_event_duration_listener(count)
    assert len(out) == len(PROMPTS) and all(g.done for g in out)
    assert eng.logits_rows_to_host > 0
    assert compiles == []
