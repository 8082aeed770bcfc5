"""Serving engine: batched generation over fixed slots with continuous
batching (finished sequences are replaced without stopping the batch), on
bf16 or **packed-quantised** weights (the paper's formats as a serving
feature: the full ~4× weight-stream cut over bf16 at 4 bits — two codes per
byte, nibble-unpacked in VMEM by the fused dequant_matmul kernel — with the
code stream + block scales resident end to end; no bf16 copy is ever
materialised for packed tensors, including MoE expert stacks).

Every registered family serves through ONE ragged path (the legacy lockstep
loop is gone): per-slot positions (``state["pos"]: (B,) int32``) and batched
chunked prefill — slots admit ragged prompt lengths without lockstep
padding, and prompts stream through ``decode_step`` in chunks of
``prefill_chunk`` tokens (decode-phase slots ride along in the same call,
one valid token each; recurrent families run their block-parallel
wkv/ssd forms over the chunk). Per-request state is the invariant: when a
slot is reused, the engine raises a ``batch["reset"]`` bit and the family's
jitted step zeroes that slot's KV rows and recurrent/conv/ssm state before
any new token is processed — no host round-trip, and no request ever
observes its predecessor's state. Encoder-decoder families additionally get
per-slot cross-attention prefill: ``ModelFamily.cross_prefill`` runs once
per admitted request (on its ``Request.frames``, or zeroing the slot when
absent) and is scattered into that slot's state rows.

Fault tolerance (the serving robustness layer; drills in ``serve.faults``):

* **slot quarantine** — a slot whose emitted logits go non-finite is
  evicted alone (``Generation.failed`` + reason, state wiped via the
  ``batch["reset"]`` protocol) and the wave keeps decoding; co-batched
  generations are unaffected (per-slot state independence).
* **per-request deadlines** — ``Request.deadline_steps`` bounds how many
  engine steps a request may occupy a slot; exceeding it quarantines the
  request instead of letting one runaway generation starve admission.
* **watchdog** — ``run(deadline_s=...)`` bounds wall-clock: an engine
  stalled by slow steps returns resumable partials instead of hanging.
* **step retry + degraded mode** — transient device-step failures re-run
  through the shared ``train.fault_tolerance.retry`` helper
  (``step_retries``); a persistent failure on packed weights triggers the
  one-time dense fallback (``dense_fallback``): every PackedTensor leaf is
  dequantised and the engine keeps serving, mirroring the
  ``windowed_cache=False`` kill-switch pattern.
* **load-time integrity** — ``from_quantised(validate=True)`` runs
  ``QuantisationPlan.verify_packed`` over the packed checkpoint and fails
  fast naming the corrupted tensor path (``validate=False`` opts out).

The engine is the slot/step substrate; the production front end lives one
layer up in ``serve.scheduler``, which wires into ``admission_hook`` /
``on_admit`` (called on every admission pass — including the mid-wave
refill at the end of each ``step_once``) to release arrivals by
priority+aging and to fork pooled shared-prefix KV into freshly seated
slots. ``step_once`` is public for that front end's cooperative
streaming; ``run`` remains the drain-everything loop.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tensor_format import PackedTensor
from repro.models.api import ModelConfig, ParamSpec, get_family
from repro.train.fault_tolerance import retry

# a ``serve.*`` span in the profiler's trace, on the device trace's clock
# (about a microsecond when no trace is active)
_span = jax.profiler.TraceAnnotation


def alloc_decode_state(fam, cfg: ModelConfig, batch_slots: int, kv_len: int,
                       *, slack: int, windowed: bool = True):
    """Allocate zeroed decode state from a family's grouped cache specs.

    The single spec→zeros call both the engine and :func:`greedy_generate`
    allocate through, so library/test decodes share the engine's cache
    geometry (same slack + windowed semantics) instead of drifting.
    ``slack`` is the prefill chunk length: cache rows past ``kv_len`` that
    chunk writes may spill into (and the ring-length margin; see
    serve.cache)."""
    specs = fam.decode_state_specs(cfg, batch_slots, kv_len, slack=slack,
                                   windowed=windowed)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def host_to_device(buf: np.ndarray):
    """The one blessed staging path for host buffers the engine mutates
    in place (slot positions, reset masks). ``jnp.asarray`` may alias a
    numpy buffer zero-copy on the CPU backend, so without a snapshot the
    jitted step can observe mutations made *after* the step was assembled
    — the PR 4 ``_slot_pos``/``_needs_reset`` aliasing bug. The static
    ``host-aliasing`` rule (``repro.analysis``) flags direct
    ``jnp.asarray`` of an in-place-mutated buffer; routing through this
    helper is the sanctioned escape hatch."""
    return jnp.asarray(buf.copy())


def _pick(logits, t_valid):
    """Each slot's last valid row of a (B, T, V) step output, at
    ``max(t_valid - 1, 0)``: its argmax (ties to the lowest index, as
    ``np.argmax``), whether it is finite, and the (B, V) rows."""
    at = jnp.maximum(t_valid - 1, 0)[:, None, None]
    rows = jnp.take_along_axis(logits, at, axis=1)[:, 0]
    return (jnp.argmax(rows, -1).astype(jnp.int32),
            jnp.isfinite(rows).all(-1), rows)


class Picked(NamedTuple):
    """One step's output as the host holds it: each slot's greedy token
    and whether its last valid logits row is finite, copied every step,
    and those (B, V) rows themselves, copied to the host (numpy) only on a
    step where a slot that emits samples at ``temperature > 0``, else left
    on the device."""
    token: np.ndarray
    finite: np.ndarray
    rows: Union[np.ndarray, jax.Array]

    @property
    def shape(self):
        """(B, V): the rows the tokens were picked from."""
        return self.rows.shape


@dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    rid: int = 0
    # encoder-decoder families: per-request encoder input ((enc_seq, D)
    # frame embeddings for whisper), encoded once at slot admission via
    # ModelFamily.cross_prefill. None = text-only (zero cross KV).
    frames: Optional[np.ndarray] = None
    # per-request deadline: max engine steps this request may occupy a slot
    # (prefill chunks + decode steps). Exceeding it quarantines the request
    # (Generation.failed, partial tokens kept) so one runaway generation
    # can never starve admission. None = no deadline.
    deadline_steps: Optional[int] = None


@dataclass
class Generation:
    rid: int
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    # the request hit the KV budget before max_new_tokens (only reachable
    # with strict_admission=False — strict engines reject such requests)
    truncated: bool = False
    # the request was quarantined (non-finite logits, deadline exceeded):
    # partial tokens are kept, done stays False, and fail_reason says why
    failed: bool = False
    fail_reason: str = ""
    # latency accounting (``time.monotonic()`` stamps; 0.0 = not reached):
    # the result object carries its own lifecycle times so latency metrics
    # (TTFT, per-token) are read off the generation, not reconstructed by
    # the caller. queue_steps is how many engine steps the request waited
    # between submit and admission (the step-clock analogue of
    # t_admit - t_submit, immune to wall-clock noise).
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    queue_steps: int = 0


class ServeEngine:
    """Fixed-slot continuous-batching decode engine.

    All families decode through the single ragged path: per-slot positions,
    batched chunked prefill, and in-step per-slot state reset on admission.
    Weights may be held packed (``from_quantised``) so the hot loop reads
    the quantised stream the kernel dequantises on the fly.

    Decode state is allocated from the family's **grouped cache specs**
    (``serve.cache``): one ``k{g}``/``v{g}`` stack per window-homogeneous
    layer group — global groups at the full ``kv_len`` (+ chunk slack),
    local (windowed) groups as ring buffers of only ``window + slack``
    slots written at ``pos % length`` (~6× less resident cache on gemma3's
    5:1 local:global pattern at serving lengths). ``windowed_cache=False``
    is the masked-full-cache baseline/kill-switch: same grouped layout,
    every group allocated at full length.

    ``strict_admission`` (default True): reject requests whose
    ``prompt + max_new_tokens`` cannot fit the KV budget at ``submit`` time.
    The budget is ``kv_len`` — the **global-layer** cache length: ring
    groups wrap and can never overflow, so only the full-length global
    caches (and the position range) constrain admission, and the budget is
    identical with or without the windowed allocation. With
    ``strict_admission=False`` such requests are admitted and end early
    with ``Generation.truncated`` set instead.

    Fault tolerance: ``step_retries`` re-runs a failed device step through
    the shared :func:`repro.train.fault_tolerance.retry` helper (1 = no
    retry); a failure that survives retry on an engine holding packed
    weights triggers the one-time **dense fallback** (``dense_fallback``,
    default True): every PackedTensor leaf is dequantised, a single
    RuntimeWarning fires, and serving continues — disable it to let the
    failure propagate. Non-finite logits quarantine only the offending
    slot (see :meth:`run`).

    Measurement: ``step_once`` writes ``serve.*`` spans into the profiler's
    trace and keeps plain counters, always on; each ``serve.step`` span
    ends with the :meth:`counters` snapshot, so a trace carries them.
    """

    def __init__(self, cfg: ModelConfig, params, batch_slots: int = 4,
                 kv_len: int = 256, prefill_chunk: int = 8,
                 strict_admission: bool = True, windowed_cache: bool = True,
                 step_retries: int = 1, dense_fallback: bool = True,
                 quantised_cache: bool = True):
        # quantised_cache=False is the KV-format kill-switch: the engine
        # drops cfg.kv_format before any state or step is built, so decode
        # runs the dense bit-exact pre-quantisation path regardless of what
        # the config asks for (the cache analogue of windowed_cache=False).
        if not quantised_cache and cfg.kv_format:
            cfg = cfg.replace(kv_format="")
        self.quantised_cache = quantised_cache
        self.cfg = cfg
        self.fam = get_family(cfg.family)
        if not getattr(self.fam, "supports_ragged", False):
            raise ValueError(
                f"family {cfg.family!r} does not implement the ragged "
                "serving protocol (supports_ragged) — per-slot positions, "
                "t_valid chunks and the reset mask are required to serve; "
                "see ModelFamily in repro.models.api")
        if step_retries < 1:
            raise ValueError(f"step_retries must be >= 1, got {step_retries}")
        self.params = params
        self.B = batch_slots
        self.kv_len = kv_len
        self.prefill_chunk = max(1, prefill_chunk)
        self.strict_admission = strict_admission
        self.windowed_cache = windowed_cache
        self.step_retries = step_retries
        self.dense_fallback = dense_fallback
        self.degraded = False     # dense fallback engaged (degrade_to_dense)
        self.degrade_reason = ""  # why it engaged (the failing step's error)
        # engine step clock: device steps executed over the engine lifetime
        # (prefill chunks + decode steps), plus the prefill-phase breakdown
        # the shared-prefix benchmarks compare (prefill_slot_steps counts
        # slot×step prefill work — the unit prefix reuse saves)
        self.steps_total = 0
        self.prefill_steps = 0
        self.prefill_slot_steps = 0
        # work counted where it happens (see counters()): tokens the steps
        # had to process against the token rows they computed (B x T), tokens
        # emitted, bytes of the step's pick copied to the host, the steps
        # that copied the last logits rows for sampling, and the bytes of
        # recurrent state the steps read and wrote
        self.tokens_valid = 0
        self.tokens_computed = 0
        self.tokens_emitted = 0
        self.logits_host_bytes = 0
        self.logits_rows_to_host = 0
        self.recurrent_state_bytes = 0
        # front-end hooks (see serve.scheduler). admission_hook(engine) runs
        # before every slot-fill pass — a scheduler releases arrivals into
        # the queue (priority/aging order) there; on_admit(engine, slot,
        # request, generation) runs after a slot is seated — a scheduler
        # forks pooled shared-prefix KV into the slot there.
        self.admission_hook = None
        self.on_admit = None
        self._state = self._zero_state()
        self._recurrent_step_bytes = 2 * self.cache_bytes()["recurrent"]
        self._slots: List[Optional[Generation]] = [None] * batch_slots
        self._queue: List[Request] = []
        self._slot_pos = np.zeros(batch_slots, np.int32)
        self._slot_steps = np.zeros(batch_slots, np.int64)  # deadline clock
        self._slot_prompt: List[List[int]] = [[] for _ in range(batch_slots)]
        # slots admitted since the last step: their first step carries
        # batch["reset"] so the jitted step wipes the predecessor's state
        # (quarantine raises the same bit to wipe a poisoned slot)
        self._needs_reset = np.zeros(batch_slots, bool)

        def serve_step(params, state, batch):
            return self.fam.decode_step(params, state, batch, self.cfg)

        # a named function: the trace and the HLO module are jit_serve_step
        self._step = jax.jit(serve_step)
        self._pick = jax.jit(_pick)
        self._compile_pick()
        self._cross_prefill = (jax.jit(
            lambda p, f: self.fam.cross_prefill(p, f, self.cfg))
            if self.fam.cross_prefill is not None else None)
        self._zero_cross = None   # lazy text-only cross-KV template

    @classmethod
    def from_quantised(cls, cfg: ModelConfig, qparams, plan,
                       packed: bool = True, validate: bool = True, **kw):
        """Build an engine from a quantised checkpoint.

        ``packed=True`` (default) keeps every packable planned tensor in its
        quantised form — codes (nibble-packed, two per byte, for ≤16-point
        codebooks) + block scales + codebook, carried as
        :class:`PackedTensor` leaves — and serves through the fused
        ``dequant_matmul`` path; MoE expert stacks stream per expert through
        its batched lead dim, and tied embedding tables serve the logits
        matmul through the transposed variant. Tensors the family declares
        no matmul layout for (or whose format is not block-scaled ≤8-bit)
        are dequantised. A family whose ``pack_layouts`` is empty (the
        explicit cannot-pack declaration) raises immediately rather than
        silently serving dense — pass ``packed=False`` to opt into that.

        ``validate=True`` (default) integrity-checks every packed tensor at
        load (``QuantisationPlan.verify_packed``: codes within the codebook
        range, nibble/K-dim layout consistency, finite scales/codebooks,
        shape agreement) and raises
        :class:`~repro.core.tensor_format.IntegrityError` naming the
        corrupted tensor path — block-scaled formats decode a flipped scale
        or stray code to unbounded garbage, so a bad checkpoint must fail
        fast instead of poisoning every co-batched generation.
        ``validate=False`` is the escape hatch (trusted checkpoint,
        load-latency-critical path)."""
        if packed:
            layouts = get_family(cfg.family).pack_layouts(cfg)
            if not layouts:
                raise ValueError(
                    f"family {cfg.family!r} declares an empty pack layout — "
                    "no tensor can serve packed; pass packed=False to serve "
                    "dequantised dense weights")
            params = plan.pack_quantised(qparams, layouts)
            if validate:
                plan.verify_packed(params)
        else:
            params = plan.dequantise(qparams)
        return cls(cfg, params, **kw)

    # ----------------------------------------------------------------- state
    def _zero_state(self):
        # slack = prefill_chunk: chunk writes may spill past a slot's final
        # position (never visible — positions ≥ kv_len are never attended),
        # and it keeps ring-buffer clobbering outside every window
        # (ring length ≥ window + chunk - 1; see serve.cache)
        return alloc_decode_state(self.fam, self.cfg, self.B, self.kv_len,
                                  slack=self.prefill_chunk,
                                  windowed=self.windowed_cache)

    def _compile_pick(self):
        """Compile ``_pick`` for both step variants (T = 1 and T =
        ``prefill_chunk``) now, so no serving step compiles it. Every
        family's ``decode_step`` returns (B, T, vocab) float32 logits; the
        step itself is not traced here, so a step that fails to trace still
        fails inside ``_execute_step``, where the dense fallback catches
        it."""
        B = self.B
        for T in sorted({1, self.prefill_chunk}):
            self._pick.lower(
                jax.ShapeDtypeStruct((B, T, self.cfg.vocab), jnp.float32),
                jax.ShapeDtypeStruct((B,), jnp.int32)).compile()

    # ------------------------------------------------------------ accounting
    def counters(self) -> dict:
        """A flat snapshot of the engine's counters since it was built:
        steps (``steps_total``, ``prefill_steps``, ``prefill_slot_steps``),
        ``tokens_valid`` (tokens the steps had to process) against
        ``tokens_computed`` (the B x T token rows they computed),
        ``tokens_emitted``, ``logits_host_bytes`` copied to the host (each
        step's (B,) tokens and finiteness bits, and the (B, V) last rows on
        a step that copied them), ``logits_rows_to_host``, the steps that
        copied those rows because an emitting slot samples, and
        ``recurrent_state_bytes``: the decode state outside the KV cache
        and the positions (``cache_bytes()["recurrent"]``), read and
        written once by every step."""
        return {k: getattr(self, k) for k in (
            "steps_total", "prefill_steps", "prefill_slot_steps",
            "tokens_valid", "tokens_computed", "tokens_emitted",
            "logits_host_bytes", "logits_rows_to_host",
            "recurrent_state_bytes")}

    def weight_bytes(self) -> dict:
        """Resident parameter bytes, broken out so entries are comparable
        across architectures: ``codes`` (the quantised weight stream),
        ``scales`` (block-scale overhead), ``codebooks`` (f32 codepoint
        tables — tiny but per-tensor), ``packed`` = codes + scales +
        codebooks, ``dense`` (leaves served in a dense dtype), ``total``,
        plus the ``family`` tag."""
        codes = scales = codebooks = dense = 0
        for leaf in jax.tree.leaves(
                self.params, is_leaf=lambda x: isinstance(x, PackedTensor)):
            if isinstance(leaf, PackedTensor):
                codes += int(leaf.codes.size) * leaf.codes.dtype.itemsize
                scales += int(leaf.scales.size) * leaf.scales.dtype.itemsize
                # size the codebook at its actual stored dtype (the array
                # the kernel reads), not an assumed 4 bytes per entry
                cb = leaf.codebook()
                codebooks += int(cb.size) * cb.dtype.itemsize
            else:
                dense += int(leaf.size) * leaf.dtype.itemsize
        packed = codes + scales + codebooks
        return {"packed": packed, "dense": dense, "total": packed + dense,
                "codes": codes, "scales": scales, "codebooks": codebooks,
                "family": self.cfg.family}

    def cache_bytes(self) -> dict:
        """Resident decode-state bytes — the term that dominates memory at
        serving batch sizes once weights are packed. ``kv`` /
        ``uniform_kv`` / ``cache_groups`` come from the family's declared
        cache geometry (``ModelFamily.cache_spec``): the grouped
        allocation vs the flat pre-ring full-length baseline, so
        ``cache_ratio_vs_uniform`` is the measured rolling-window saving.
        ``other`` is the non-KV decode state (recurrent/conv/ssm state,
        whisper's cross-attention KV, positions), ``recurrent`` the same
        less the positions; ``total`` sums the actual allocated state
        tree."""
        total = int(sum(int(l.size) * l.dtype.itemsize
                        for l in jax.tree.leaves(self._state)))
        pos = self._state["pos"]
        out = {"total": total, "family": self.cfg.family}
        if self.fam.cache_spec is not None:
            spec = self.fam.cache_spec(
                self.cfg, self.B, self.kv_len, slack=self.prefill_chunk,
                windowed=self.windowed_cache)
            cb = spec.cache_bytes()
            out.update(cb)
            out["other"] = total - cb["kv"]
        else:
            out.update({"kv": 0, "uniform_kv": 0,
                        "cache_ratio_vs_uniform": 1.0, "cache_groups": [],
                        "other": total})
        out["recurrent"] = out["other"] - int(pos.size) * pos.dtype.itemsize
        return out

    # ------------------------------------------------------------------- api
    def submit(self, req: Request):
        """Queue a request. The prompt must always fit the KV budget; with
        ``strict_admission`` (default) the whole generation must too —
        ``prompt + max_new_tokens > kv_len`` raises instead of silently
        truncating mid-decode. Non-strict engines admit such requests and
        mark the resulting :class:`Generation` ``truncated``.

        ``kv_len`` budgets the **global-layer** cache length (and the
        position range) only: windowed layer groups are ring buffers that
        wrap at ``pos % length`` and can never overflow, so their (much
        smaller) allocation never constrains admission — a request that
        fits the global caches is admissible regardless of how far past
        any local window it runs.

        Malformed requests are rejected here, not mid-decode: an empty
        prompt (there is no token to decode from) and ``max_new_tokens <=
        0`` (the generation could never finish) raise ``ValueError``. A
        ``rid`` colliding with a queued or live request warns: sampling
        seeds from ``(rid, token index)``, so colliding rids silently draw
        identical streams."""
        self.validate_request(req)
        # latency stamps: a front end (serve.scheduler) may pre-stamp the
        # submit time/step (e.g. a replayed arrival); default to now
        if not hasattr(req, "_t_submit"):
            req._t_submit = time.monotonic()
        if not hasattr(req, "_submit_step"):
            req._submit_step = self.steps_total
        self._queue.append(req)

    def validate_request(self, req: Request) -> None:
        """The admission checks behind :meth:`submit`, callable up front by
        schedulers so a malformed or over-budget request fails at the
        caller instead of mid-replay (same checks, one source)."""
        if not req.prompt:
            raise ValueError(
                f"request rid={req.rid}: empty prompt — at least one token "
                "is required to decode from")
        if req.max_new_tokens <= 0:
            raise ValueError(
                f"request rid={req.rid}: max_new_tokens="
                f"{req.max_new_tokens} must be >= 1")
        if req.deadline_steps is not None and req.deadline_steps < 1:
            raise ValueError(
                f"request rid={req.rid}: deadline_steps="
                f"{req.deadline_steps} must be >= 1 (or None)")
        active = {r.rid for r in self._queue} | {
            g.rid for g in self._slots if g is not None}
        if req.rid in active:
            warnings.warn(
                f"submit: rid={req.rid} collides with a queued or live "
                "request — sampling seeds per (rid, token index), so the "
                "two streams will be identical at temperature > 0; use "
                "unique rids", RuntimeWarning, stacklevel=2)
        if len(req.prompt) >= self.kv_len:
            raise ValueError(
                f"request rid={req.rid}: prompt length {len(req.prompt)} "
                f"does not fit the KV budget (kv_len={self.kv_len})")
        if self.strict_admission and \
                len(req.prompt) + req.max_new_tokens > self.kv_len:
            raise ValueError(
                f"request rid={req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds the KV "
                f"budget (kv_len={self.kv_len}) — the generation would be "
                "truncated; shrink the request or build the engine with "
                "strict_admission=False to accept truncated generations")

    def run(self, max_steps: int = 512,
            deadline_s: Optional[float] = None) -> List[Generation]:
        """Drive decode until queue + slots drain, or ``max_steps`` expires,
        or the ``deadline_s`` wall-clock watchdog fires.

        Returns every generation that made progress: finished ones
        (``done=True``), quarantined ones (``failed=True`` with
        ``fail_reason``), and — if a budget ran out first — the still-live
        partial ones (``done=False``), with a ``RuntimeWarning`` naming the
        live-slot and still-queued counts, so callers can never silently
        receive fewer generations than they submitted. Live slots keep
        their state; calling ``run`` again continues them.

        Fault isolation: after each step the last valid logits row of
        every slot that emits is checked for finiteness, on the device (the
        host copies one bit a slot). A non-finite row
        quarantines **only that slot** — the generation is returned
        ``failed`` with its partial tokens, the slot is evicted and its
        (possibly poisoned) state wiped through the ``batch["reset"]``
        protocol on the next step — while every co-batched generation
        keeps decoding undisturbed (per-slot state independence is the
        ragged path's invariant). ``Request.deadline_steps`` quarantines
        the same way when a request overstays its step budget. A device
        step that fails after ``step_retries`` attempts degrades the
        engine to dense weights (``dense_fallback``) instead of dying."""
        finished: List[Generation] = []
        t0 = time.monotonic()
        watchdog_fired = False
        for _ in range(max_steps):
            if deadline_s is not None and time.monotonic() - t0 > deadline_s:
                watchdog_fired = True
                break
            if not self.step_once(finished):
                break
        # Expiry accounting under mid-wave admission: a slot seated by the
        # refill at the end of the final step has never executed a device
        # step — it is indistinguishable from a queued request, so un-admit
        # it (requeue the Request at the front, discard the Generation; the
        # slot's reset bit stays raised) and count it as queued below.
        # Returning it as a zero-progress "live" partial would both
        # misreport progress and hand the caller a Generation that a
        # resumed run() re-admits as a fresh one.
        requeue: List[Request] = []
        for i, g in enumerate(self._slots):
            if g is not None and self._slot_steps[i] == 0:
                requeue.append(g._req)  # type: ignore
                self._slots[i] = None
        self._queue[:0] = requeue
        live = [g for g in self._slots if g is not None]
        if watchdog_fired:
            warnings.warn(
                f"ServeEngine.run: wall-clock watchdog deadline_s="
                f"{deadline_s} expired after {time.monotonic() - t0:.2f}s "
                f"with {len(live)} live slot(s) and {len(self._queue)} "
                "queued request(s); partial generations are returned with "
                "done=False and resume on the next run() call",
                RuntimeWarning, stacklevel=2)
            finished.extend(live)
        elif live or self._queue:
            # max_steps expired mid-flight: surface the truncation instead
            # of silently returning fewer generations than were submitted
            warnings.warn(
                f"ServeEngine.run: max_steps={max_steps} expired with "
                f"{len(live)} live slot(s) and {len(self._queue)} queued "
                "request(s); partial generations are returned with "
                "done=False and resume on the next run() call",
                RuntimeWarning, stacklevel=2)
            finished.extend(live)
        return finished

    def step_once(self, finished: List[Generation]) -> bool:
        """One continuous-batching iteration: admit (front-end hook + slot
        fill), execute one device step over the live slots, emit/quarantine
        per slot, then **refill any slot freed mid-wave** — a finished or
        quarantined slot is reclaimed inside the same iteration, so
        admission never waits for a wave to drain. Generations completing
        during the step are appended to ``finished``. Returns False (no
        step executed) when there is nothing to do — no live slot and the
        admission pass produced none.

        The iteration is a ``serve.step`` span (``T``, ``live``,
        ``prefill_rows``; at its end the :meth:`counters` snapshot) around
        one span per host phase, in order: ``serve.admit`` (hook and slot
        fill, one ``serve.seat`` per request seated), ``serve.assemble``
        (the batch and its copy to the device), ``serve.dispatch`` (the
        enqueue of the step, of ``_pick`` and of the copies, a compile or a
        retry), ``serve.device_wait``, ``serve.logits_to_host`` (what of the
        copy the wait did not cover), ``serve.sample`` and ``serve.refill``.
        The step's logits stay on the device: ``_pick`` takes each slot's
        last valid row there, and the host copies its (B,) argmax tokens
        and finiteness bits, and the (B, V) rows only on a step where an
        emitting slot samples at ``temperature > 0``."""
        with _span("serve.step") as span:
            with _span("serve.admit"):
                self._admit()
            if all(s is None for s in self._slots):
                return False
            with _span("serve.assemble"):
                batch, t_valid, prefill_rows = self._assemble()
            T = batch["tokens"].shape[1]
            span.set_metadata(
                T=T, live=sum(g is not None for g in self._slots),
                prefill_rows=len(prefill_rows))
            with _span("serve.dispatch"):
                logits, self._state = self._execute_step(batch)
                token, finite, rows = self._pick(logits, batch["t_valid"])
                copy_rows = self._needs_rows(t_valid)
                wanted = (token, finite) + ((rows,) if copy_rows else ())
                # each copy starts when the device has computed it, not
                # when the host next looks
                for a in wanted:
                    a.copy_to_host_async()
            with _span("serve.device_wait"):
                jax.block_until_ready((token, finite))
            with _span("serve.logits_to_host"):
                copied = jax.device_get(wanted)
            picked = Picked(*copied) if copy_rows else Picked(*copied, rows)
            self.steps_total += 1
            if prefill_rows:
                self.prefill_steps += 1
                self.prefill_slot_steps += len(prefill_rows)
            self.tokens_valid += int(t_valid.sum())
            self.tokens_computed += self.B * T
            self.logits_host_bytes += sum(a.nbytes for a in copied)
            self.logits_rows_to_host += copy_rows
            self.recurrent_state_bytes += self._recurrent_step_bytes
            with _span("serve.sample"):
                self._sample(picked, t_valid, finished)
            # mid-wave refill: slots freed by _emit_token/_quarantine are
            # reclaimed now, inside the wave, not at the next run() pass
            with _span("serve.refill"):
                self._admit()
            span.set_metadata(**self.counters())
        return True

    def _assemble(self):
        """The next step's batch over the live slots: each prefilling slot's
        next prompt chunk (T = prefill_chunk while any slot prefills) or each
        decoding slot's last token, staged on the device. Returns (batch,
        t_valid, prefilling slots)."""
        prefill_rows = [
            i for i, g in enumerate(self._slots)
            if g is not None and self._slot_pos[i] < len(self._slot_prompt[i])]
        T = self.prefill_chunk if prefill_rows else 1
        toks = np.zeros((self.B, T), np.int32)
        t_valid = np.zeros(self.B, np.int32)
        for i, g in enumerate(self._slots):
            if g is None:
                continue
            consumed = int(self._slot_pos[i])
            prompt = self._slot_prompt[i]
            if consumed < len(prompt):        # prefill: next chunk
                v = min(T, len(prompt) - consumed)
                toks[i, :v] = prompt[consumed:consumed + v]
            else:                             # decode: last sampled token
                v = 1
                toks[i, 0] = g.tokens[-1]
            t_valid[i] = v
        # _slot_pos/_needs_reset are mutated in place below; the device
        # must see this iteration's snapshot (see host_to_device)
        self._state["pos"] = host_to_device(self._slot_pos)
        batch = {"tokens": jnp.asarray(toks),
                 "t_valid": jnp.asarray(t_valid)}
        # "reset" rides only on steps that admitted (or quarantined) a
        # slot: steady-state decode never pays the cache-wide where.
        # Admission always prefills, so the step compiles 3 trace
        # variants in normal operation (T=chunk ± reset, T=1), each
        # once per engine lifetime; a quarantine on a decode step may
        # add the rare fourth (T=1 + reset).
        if self._needs_reset.any():
            batch["reset"] = host_to_device(self._needs_reset)
            self._needs_reset[:] = False
        return batch, t_valid, prefill_rows

    def _needs_rows(self, t_valid: np.ndarray) -> bool:
        """Whether a slot that emits a token this step samples at
        ``temperature > 0``, and so needs its logits row on the host."""
        return any(
            g is not None and g._req.temperature > 0  # type: ignore
            and self._slot_pos[i] + t_valid[i] >= len(self._slot_prompt[i])
            for i, g in enumerate(self._slots))

    def _sample(self, picked: Picked, t_valid: np.ndarray,
                finished: List[Generation]):
        """Advance every live slot by its valid tokens; each slot past its
        prompt emits a token picked from its last valid logits row, or is
        quarantined if that row is not finite; then the deadline check."""
        for i, g in enumerate(self._slots):
            if g is None:
                continue
            self._slot_pos[i] += int(t_valid[i])
            self._slot_steps[i] += 1
            if self._slot_pos[i] >= len(self._slot_prompt[i]):
                if picked.finite[i]:
                    self._emit_token(i, g, picked, finished)
                else:
                    self._quarantine(
                        i, g, "non-finite logits at token index "
                        f"{len(g.tokens)}", finished)
                    continue
            g = self._slots[i]
            if g is not None:                 # deadline check
                dl = g._req.deadline_steps  # type: ignore
                if dl is not None and self._slot_steps[i] >= dl:
                    self._quarantine(
                        i, g, f"deadline_steps={dl} exceeded with "
                        f"{len(g.tokens)} token(s) generated", finished)

    # --------------------------------------------------- fault tolerance
    def _execute_step(self, batch):
        """One device step, with the robustness ladder around it: transient
        failures re-run through the shared ``retry`` helper
        (``step_retries`` total attempts); a failure that survives retry on
        an engine still holding packed weights triggers the one-time dense
        fallback and re-executes on the dequantised params."""
        call = lambda: self._step(self.params, self._state, batch)
        try:
            if self.step_retries > 1:
                return retry(call, max_attempts=self.step_retries)
            return call()
        except (RuntimeError, ValueError, OSError) as e:
            if not (self.dense_fallback and not self.degraded
                    and self._has_packed()):
                raise
            self.degrade_to_dense(reason=f"device step failed: {e!r}")
            return call()

    def _has_packed(self) -> bool:
        return any(isinstance(l, PackedTensor) for l in jax.tree.leaves(
            self.params, is_leaf=lambda x: isinstance(x, PackedTensor)))

    def degrade_to_dense(self, reason: str = "operator request") -> None:
        """Degraded-mode kill-switch: dequantise every PackedTensor leaf
        and keep serving on dense weights (one-time RuntimeWarning; decode
        state and live generations are untouched, and the next step simply
        retraces against the dense pytree). The runtime analogue of the
        ``windowed_cache=False`` layout kill-switch — flip it when the
        packed matmul path itself is the suspect. Idempotent."""
        if self.degraded:
            return
        self.degraded = True
        self.degrade_reason = reason
        n = sum(1 for l in jax.tree.leaves(
            self.params, is_leaf=lambda x: isinstance(x, PackedTensor))
            if isinstance(l, PackedTensor))
        self.params = jax.tree.map(
            lambda x: x.dequantise() if isinstance(x, PackedTensor) else x,
            self.params, is_leaf=lambda x: isinstance(x, PackedTensor))
        warnings.warn(
            f"ServeEngine: degraded mode — {n} packed tensor(s) "
            f"dequantised to dense, packed matmul path bypassed ({reason}); "
            "the engine keeps serving", RuntimeWarning, stacklevel=2)

    def _quarantine(self, i: int, g: Generation, reason: str,
                    finished: List[Generation]) -> None:
        """Evict slot ``i`` alone: return its generation ``failed`` (partial
        tokens kept, ``done`` stays False), free the slot for admission,
        and raise the slot's ``batch["reset"]`` bit so the jitted step
        wipes its (possibly poisoned) KV rows / recurrent state before any
        reuse — co-batched slots never observe the fault."""
        g.failed = True
        g.fail_reason = reason
        g.t_done = time.monotonic()
        finished.append(g)
        self._slots[i] = None
        self._needs_reset[i] = True
        warnings.warn(
            f"ServeEngine: quarantined slot {i} (rid={g.rid}): {reason}; "
            "remaining slots continue undisturbed", RuntimeWarning,
            stacklevel=3)

    # ------------------------------------------------------------- internals
    def _admit(self):
        """One admission pass: give the front-end hook a chance to release
        arrivals into the queue (priority order, virtual-clock release —
        see serve.scheduler), then seat queued requests into free slots."""
        if self.admission_hook is not None:
            self.admission_hook(self)
        self._fill_slots()

    def _fill_slots(self):
        for i in range(self.B):
            if self._slots[i] is None and self._queue:
                req = self._queue.pop(0)
                with _span("serve.seat", rid=req.rid, slot=i):
                    self._seat(i, req)

    def _seat(self, i: int, req: Request):
        """Seat ``req`` in free slot ``i``: a new generation, the slot's
        prompt and clocks, its reset bit, then the admission hooks."""
        g = Generation(rid=req.rid)
        g.t_submit = getattr(req, "_t_submit", 0.0)
        g.t_admit = time.monotonic()
        g.queue_steps = self.steps_total - getattr(
            req, "_submit_step", self.steps_total)
        self._slots[i] = g
        g._req = req  # type: ignore
        self._slot_prompt[i] = list(req.prompt)
        self._slot_pos[i] = 0
        self._slot_steps[i] = 0           # deadline clock restarts
        # the first step after admission carries reset[i]=True: the
        # jitted step zeroes the slot's KV rows and recurrent state
        # (the predecessor's) before this prompt's first token
        self._needs_reset[i] = True
        if self._cross_prefill is not None:
            self._admit_cross(i, req)
        # front-end hook: a scheduler forks pooled shared-prefix KV
        # into the seated slot here (pure state surgery — may move
        # _slot_pos past the pooled prefix and clear the reset bit)
        if self.on_admit is not None:
            self.on_admit(self, i, req, g)

    def _admit_cross(self, i: int, req: Request):
        """Per-slot cross-attention prefill: encode this request's frames
        (or zeros for text-only) and scatter into slot i's state rows —
        cross KV is owned by admission, not by the in-step reset mask."""
        if req.frames is not None:
            frames = jnp.asarray(req.frames)[None]      # (1, enc_seq, D)
            entries = self._cross_prefill(self.params, frames)
        else:
            # the text-only wipe is a constant zero template per engine —
            # build it once, not per admission
            if self._zero_cross is None:
                self._zero_cross = self.fam.cross_prefill(self.params, None,
                                                          self.cfg)
            entries = self._zero_cross
        for key, val in entries.items():
            self._state[key] = self._state[key].at[:, i].set(val[:, 0])

    def _emit_token(self, i: int, g: Generation, picked: Picked,
                    finished: List[Generation]):
        """Slot ``i`` emits its next token: the device's argmax, or at
        ``temperature > 0`` one drawn from its copied logits row."""
        req = g._req  # type: ignore
        if req.temperature > 0:
            z = picked.rows[i] / req.temperature
            p = np.exp(z - z.max())
            p /= p.sum()
            # seed from (rid, index): decoupled across slots — one stream
            # per request, reproducible for a given rid regardless of which
            # slot or wave it lands in. Masked to uint32: SeedSequence
            # rejects negative entries, and rid<0 is a valid id (the
            # benchmarks use rid=-1 for warmup requests)
            rng = np.random.default_rng((req.rid & 0xFFFFFFFF,
                                         len(g.tokens)))
            tok = int(rng.choice(len(p), p=p))
        else:
            tok = int(picked.token[i])
        if not g.tokens:
            g.t_first_token = time.monotonic()
        g.tokens.append(tok)
        self.tokens_emitted += 1
        hit_budget = len(g.tokens) >= req.max_new_tokens
        hit_kv = self._slot_pos[i] >= self.kv_len - 1
        if hit_budget or hit_kv:
            g.done = True
            g.truncated = bool(hit_kv and not hit_budget)
            g.t_done = time.monotonic()
            finished.append(g)
            self._slots[i] = None
    # ------------------------------------------------------------------------


def greedy_generate(cfg: ModelConfig, params, prompt: np.ndarray,
                    n_new: int, kv_len: int = 256):
    """Single-sequence greedy decode (library utility + tests). Allocates
    through the same :func:`alloc_decode_state` call as the engine — one
    token per step, so ``slack=1`` is its prefill-chunk length."""
    fam = get_family(cfg.family)
    state = alloc_decode_state(fam, cfg, prompt.shape[0], kv_len, slack=1)
    step = jax.jit(lambda p, s, b: fam.decode_step(p, s, b, cfg))
    out = []
    tok = prompt[:, :1]
    for t in range(prompt.shape[1] + n_new - 1):
        logits, state = step(params, state, {"tokens": jnp.asarray(tok)})
        if t + 1 < prompt.shape[1]:
            tok = prompt[:, t + 1: t + 2]
        else:
            tok = np.asarray(jnp.argmax(logits[:, 0], -1))[:, None]
            out.append(tok[:, 0])
    return np.stack(out, 1)
