"""repro.serve — production-style serving on the paper's quantised formats.

The deployment half of the paper's claim: block-scaled codebook formats cut
the weight stream ~4× at 4 bits, and the serving path realises it by never
materialising a dense copy of planned tensors — for **every** family in the
zoo, because weight application goes through one projection API.

The unified projection API
--------------------------
Every model family applies parameters exclusively through
``models.layers.linear(x, w, spec)`` (plus ``embed_lookup`` for token
gathers and ``expert_matmul`` for MoE stacks). The einsum spec documents
the dense semantics and drives the packed dispatch: when ``w`` is a
:class:`repro.core.PackedTensor`, a spec whose weight subscripts lead with
the contracting labels routes through the fused ``dequant_matmul`` kernel,
and a spec whose weight subscripts *end* with them (``"btd,vd->btv"`` —
tied embeddings) routes through the transposed ``dequant_matmul_t``
variant, contracting along the blocked axis so ``unembed = embed.T`` never
materialises. Dense weights take the exact einsum the call site always
used. There are no per-family special cases: packed serving is a property
of the system, declared per tensor in ``ModelFamily.pack_layouts``
(required — a family that cannot pack registers
``models.api.empty_pack_layouts`` and ``from_quantised(packed=True)``
fails fast instead of silently serving dense).

Components
----------
``engine.ServeEngine``
    Fixed-slot continuous-batching engine. Two weight representations:

    * dense (bf16/f32) params — the bit-identical baseline path;
    * **packed** params (``ServeEngine.from_quantised``): each planned
      tensor stays codes + bf16 block scales + codebook
      (:class:`repro.core.PackedTensor`). Codebooks of ≤16 points store
      **two codes per byte** (``bits=4``, the K-dim nibble interleave of
      ``core.nibble``) — the paper's full ~4× resident/stream cut over
      bf16, ~7.5× vs the f32 master — and every matmul routes through the
      fused ``kernels.ops.dequant_matmul`` / ``dequant_matmul_t`` pair
      (Pallas on TPU with in-VMEM nibble unpack, jnp oracle off-TPU). MoE
      expert stacks (``we_gate``/``we_up``/``we_down``) stream per expert
      through the kernel's batched lead dim inside ``moe_block`` instead
      of being densified (the EP shard_map path logs once and falls back
      to local dispatch for packed stacks). Embedding rows
      gather-dequantise on the fly (byte row + nibble select for 4-bit
      tables), honouring the serving dtype; tied tables additionally serve
      the logits matmul transposed.

    Every registered family decodes through ONE ragged path (the legacy
    lockstep loop is gone): **per-slot KV positions** (``state["pos"]:
    (B,) int32``) and **batched chunked prefill** — slots admit ragged
    prompt lengths with no lockstep padding; prompts stream through
    ``decode_step`` in ``prefill_chunk``-token chunks while decode-phase
    slots ride along in the same call (one valid token each; rwkv6/zamba2
    run their block-parallel wkv/ssd forms over the chunk). Per-request
    state is the invariant: reusing a slot raises a ``batch["reset"]`` bit
    and the family's jitted step zeroes that slot's KV rows and
    recurrent/conv/ssm state before the new prompt's first token — no host
    round-trip, no cross-request leak. whisper additionally gets per-slot
    cross-attention prefill (``ModelFamily.cross_prefill`` encodes each
    admitted request's ``Request.frames`` — or zeroes the slot — instead
    of one engine-global encoding). ``submit`` enforces the KV budget:
    requests whose prompt + max_new_tokens cannot fit are rejected
    (``strict_admission=False`` admits them and flags the result
    ``Generation.truncated``).

    Decode state is allocated from **grouped ring-buffer cache specs**
    (``cache.CacheSpec``/``CacheGroup``): every attention-bearing family
    declares its cache geometry as window-homogeneous layer groups
    (``k{g}``/``v{g}`` state stacks), where global groups allocate the
    full ``kv_len`` (+ chunk slack) and local (windowed) groups allocate
    a **ring buffer** of only ``window + slack`` slots written at
    ``pos % length``. Attention masks are rebuilt from reconstructed slot
    positions (``cache.ring_positions``), so wrap-around, chunked prefill
    crossing the wrap boundary, and slot reuse need no extra bookkeeping
    — greedy tokens stay identical to the masked full-cache baseline
    (``windowed_cache=False``, the layout kill-switch), and admission
    still budgets ``prompt + max_new_tokens`` against the global-layer
    length only (rings never overflow). On gemma3's 5:1 local:global
    pattern this cuts resident cache ~6× at serving lengths (asymptote
    26/4 layers; 0.23× the uniform cache at smoke size, kv_len=256).

    The cache *contents* quantise too (``cfg.kv_format``): each cache
    group stores block-scaled codebook rows instead of dense activations
    — uint8 codes (``k{g}``/``v{g}``, nibble-packed pairwise along the
    head dim for q4) plus one f32 absmax scale per (token, head) row
    (``k{g}s``/``v{g}s``, scale block = head_dim) — the paper's weight
    formats applied to the decode-time KV stream. Writes quantise fresh
    rows inside the jitted step (``layers.update_kv_cache`` on a
    ``QuantisedKV`` stack); reads stream codes straight through the fused
    ``kernels.decode_attention`` flash-decode kernel (dequantise in VMEM
    after the HBM read, identical ring/window/causal mask semantics). A
    q8 row holds head_dim code bytes plus one 4-byte scale against
    4 · head_dim bytes in f32, 0.27× at head_dim 64 (q4 0.14×): a byte
    ratio, not a measured speed. Every row is self-contained, so ring
    wraps, ragged chunk padding, slot resets (a zero scale dequantises to
    the dense wipe) and PrefixPool forks (``CacheSpec.state_keys`` enumerates the
    scale entries) work unchanged. ``kv_format`` is per group
    (``"q8"``/``"q4"`` broadcast, or a comma list — whisper's
    cross-attention KV always stays dense), chosen by hand or by the
    Fisher machinery: ``core.fisher.estimate_kv_fisher`` scores each
    group's cache rows by the paper's Eq. 5 sensitivity and
    ``core.allocation.allocate_kv_formats`` demotes least-sensitive
    groups first under a resident-byte budget (``launch.serve
    --kv-format auto --kv-budget-bytes N``). An empty ``kv_format`` is
    the dense cache.

    ``ServeEngine.weight_bytes()`` reports resident bytes broken out as
    codes / scales / codebooks / dense (comparable across architectures);
    ``ServeEngine.cache_bytes()`` reports the decode-cache side — per
    cache group (windowed vs global, with the code/scale byte split and
    per-group format) against the uniform full-length dense baseline.
    ``chipbench/`` measures the serving path on the chip.

The scheduling front end
------------------------
``scheduler.Scheduler`` is the production serving loop over one engine —
the layer that turns drain-the-queue batch decoding into a front end real
traffic can hit:

* **submit/stream lifecycle** — ``Scheduler.submit(prompt, priority=...,
  prefix=..., at=...)`` validates eagerly (the engine's own KV-budget
  check, so malformed requests fail at the caller) and returns a
  ``StreamHandle`` immediately; ``handle.stream()`` yields tokens as they
  are decoded by cooperatively driving ``ServeEngine.step_once`` (the
  single-threaded analogue of an async server loop), ``handle.result()``
  blocks to completion, and ``Scheduler.run()`` drains everything.
  Admission is **continuous**: the engine calls the scheduler back before
  every slot-fill pass — including the mid-wave refill at the end of each
  step — so a slot freed by a finished or quarantined generation is
  reseated inside the same wave, riding the existing ``batch["reset"]``
  protocol with no new step-fn surface.
* **priority + aging** — requests are released into free slots by
  effective priority ``priority + aging * steps_waited`` (FIFO among
  ties), so higher-priority requests admit sooner but a low-priority
  request can never starve: after ``Δpriority / aging`` steps it outranks
  every fresh arrival. All scheduling runs on a **virtual step clock**
  (``submit(at=...)`` arrival times in engine steps, idle gaps
  fast-forwarded), so a replayed workload admits identically every run;
  wall-clock only appears in the latency stamps ``Generation`` carries
  (``t_submit``/``t_admit``/``t_first_token``/``t_done`` +
  ``queue_steps``).
* **shared-prefix reuse** — ``register_prefix(key, tokens)`` declares a
  common prompt prefix (system prompt, few-shot header); requests
  submitted with ``prefix=key`` prefill it **once** into the
  ``PrefixPool`` (through the engine's own jitted step, donor row 0 of a
  zeroed state) and admission *forks* the pooled KV rows into the seated
  slot: pure state surgery over every ``CacheSpec.state_keys`` entry —
  ring and global groups alike — plus the position jump, with the
  admission reset bit cleared because the full-row copy subsumes the
  wipe. Forked slots decode **bit-identically** to full recomputation
  (chunked prefill is exact); families whose per-slot state is not just
  KV + position (rwkv6/zamba2/whisper) recompute with a one-time warning
  instead. Pool entries are LRU-evicted; forks hold copies, so eviction
  never disturbs a live generation.
* **failure semantics** — the front end inherits the robustness layer
  below unchanged: a quarantined slot surfaces as its handle's
  ``failed`` generation and the freed slot is refilled in the same wave;
  the degraded-mode fallback and watchdog behave exactly as under direct
  ``engine.run``.

The robustness layer
--------------------
Serving on aggressively quantised weights concentrates failure into two
sharp modes — a corrupted packed stream decodes to unbounded garbage
(absmax block scales amplify a single flipped word), and a poisoned slot
NaNs its logits — so fault tolerance is part of the serving path, not an
afterthought. Every recovery path below has a deterministic injector in
``serve.faults`` and is drilled by ``tests/test_serve_faults.py``:

* **Load-time integrity** — ``ServeEngine.from_quantised(validate=True)``
  runs ``QuantisationPlan.verify_packed`` over the packed checkpoint:
  codes within the codebook range, nibble-parity/K-dim layout consistency,
  finite scales/codebooks, shape agreement with the declared pack layouts.
  A violation raises ``repro.core.IntegrityError`` **naming the tensor
  path** — fail fast at load beats serving garbage to every co-batched
  request. ``validate=False`` is the trusted-checkpoint escape hatch.
* **Slot quarantine** — non-finite logits evict only the offending slot:
  its ``Generation`` returns ``failed=True`` with partial tokens and a
  ``fail_reason``, its state is wiped through the same ``batch["reset"]``
  protocol admission uses, and every other slot keeps decoding
  bit-identically (per-slot state independence is the ragged path's
  invariant). ``Request.deadline_steps`` quarantines runaway requests the
  same way; ``run(deadline_s=...)`` is the wall-clock watchdog that turns
  a stalled engine into resumable partials.
* **Step retry + degraded mode** — transient device-step failures re-run
  through the shared ``train.fault_tolerance.retry`` helper
  (``ServeEngine(step_retries=N)``); a failure that survives retry on
  packed weights triggers the one-time dense fallback
  (``degrade_to_dense``): every PackedTensor leaf is dequantised, one
  RuntimeWarning fires, and the engine keeps serving — the runtime
  analogue of the ``windowed_cache=False`` layout kill-switch.
* **Admission hygiene** — ``submit`` rejects empty prompts and
  ``max_new_tokens <= 0`` up front, and warns on duplicate rids (sampling
  seeds per ``(rid, token index)``, so colliding rids silently draw
  identical streams).

``cache``
    The decode-cache subsystem: ``CacheSpec``/``CacheGroup`` geometry,
    ring-buffer index math (slot mapping + position reconstruction), and
    ``cache_bytes()`` accounting with the uniform baseline.

``faults``
    The fault-injection harness behind the drills above: checkpoint
    corruption (``corrupt_codes``/``corrupt_scales``/``corrupt_layout``),
    per-slot NaN logits, device-step failures and stalls, and admission
    drop/duplicate faults — each returning counter state so tests assert
    the fault actually fired.

``scheduler``
    The front end described above: ``Scheduler``/``StreamHandle``/
    ``PrefixPool``.

``context_parallel``
    Flash-decode attention over a sequence-sharded KV cache (exact
    log-sum-exp combine), for caches too big for one device.

Static enforcement
------------------
The invariants above are also enforced *statically*: ``repro.analysis``
(run by ``scripts/run_tests.sh`` — default fast target and ``--lint``)
lints the tree for the shipped serving bug classes — host-buffer aliasing
into the jitted step, raw weight einsums that bypass the projection API,
hidden-global nondeterminism in step paths, decode steps that skip the
t_valid/reset protocol — and abstractly verifies every registered
``ModelFamily``'s pack-layout / cache-spec / ragged-decode declarations
against its actual callables. Host buffers the engine mutates in place
(slot positions, reset masks) stage through ``engine.host_to_device`` —
the one blessed snapshot-then-transfer helper; a bare ``jnp.asarray`` of
such a buffer is a lint finding (see
``src/repro/analysis/README.md``).

Which tensors pack is declared per family (``ModelFamily.pack_layouts``)
and checked per format (``QuantisationPlan.packable``): block-scaled
codebooks of ≤256 codes whose output dim tiles by the scale block; ≤16
codes with an even contraction dim additionally nibble-pack to 4 bits.
The rest (the MoE router, formats with sparse outliers or tensor/channel
scaling, tensors whose output dim does not tile by the block — e.g.
zamba2's 548-wide in_proj in smoke) are dequantised at load.
"""
from . import (cache, context_parallel, engine, faults,  # noqa: F401
               scheduler)
from .cache import CacheGroup, CacheSpec, build_cache_spec
from .engine import Request, ServeEngine, greedy_generate, host_to_device
from .scheduler import PrefixPool, Scheduler, StreamHandle

__all__ = ["cache", "context_parallel", "engine", "faults", "scheduler",
           "CacheGroup", "CacheSpec", "build_cache_spec",
           "Request", "ServeEngine", "greedy_generate", "host_to_device",
           "PrefixPool", "Scheduler", "StreamHandle"]
