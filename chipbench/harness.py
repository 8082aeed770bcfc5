"""One run of one cell: set-up, the measured window, the reading of the
metrics and the check of what the window served.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name: ``configs/<config>.json``, ``mixes/<traffic>.json``,
``metrics/<metric>.py`` and ``reference/<family>.py``. The program is used
only through its serving entry points (``ServeEngine``, ``Scheduler``),
its counters and the kernel names in its compiled steps."""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace
from typing import List

import numpy as np

from chipbench import trace as tr
from chipbench import traffic, weights

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 2.0       # a traced run traces the last this-many seconds


# ----------------------------------------------------------------- the cell

@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    e2e: List[dict]
    per_layer: List[dict]

    @property
    def ref(self):
        return importlib.import_module(
            f"chipbench.reference.{self.config['family']}")


def load_cell(bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    mine = lambda m: "workloads" not in m or name in m["workloads"]
    return Cell(name=name, config=config, mix=traffic.load_mix(w["traffic"]),
                chips=w["chips"],
                e2e=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def reader(metric: str):
    """The reader of ``metrics/<metric>.py``, or, for a metric named
    ``<name>.<group>`` without a file of its own, of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(config: dict):
    from repro.models.api import ModelConfig
    return ModelConfig(name=config["name"], family=config["family"],
                       **config["model"])


# ------------------------------------------------------------------ set-up

def served_params(layout, coded, config: dict):
    """The checkpoint the program loads: every tensor its plan quantises as
    the program's ``QuantisedTensor`` of the harness's codes and scales, the
    rest as float32 values."""
    import jax
    from repro.core import build_plan
    from repro.core.tensor_format import QuantisedTensor
    w = config["weights"]
    flat, tree = jax.tree_util.tree_flatten_with_path(
        coded, is_leaf=lambda x: isinstance(x, weights.Coded))
    shape = jax.tree_util.tree_leaves(weights.shapes(layout),
                                      is_leaf=lambda x: isinstance(x, tuple))
    plan = build_plan(jax.tree_util.tree_unflatten(
        tree, [jax.ShapeDtypeStruct(s, "float32") for s in shape]),
        w["format"])
    decode = jax.jit(weights.dense, static_argnums=(2, 3))
    out = []
    for (path, c), s in zip(flat, shape):
        if plan.formats.get(jax.tree_util.keystr(path)) is not None:
            out.append(QuantisedTensor(c.codes, c.scales, None, None, s,
                                       "float32"))
        else:
            out.append(decode(c.codes, c.scales, tuple(w["codepoints"]), s))
    return plan, jax.tree_util.tree_unflatten(tree, out)


def build_engine(cell: Cell, seed: int):
    """Weights from the seed in their served form (one jitted call), the
    program's packer, and a packed engine with the dense fallback off."""
    import jax
    from repro.models.api import ParamSpec, get_family
    from repro.serve.engine import ServeEngine
    cfg = program_config(cell.config)
    model = cell.config["model"]
    layout = cell.ref.layout(model)
    specs = get_family(cfg.family).param_specs(cfg)
    want = jax.tree.map(lambda s: tuple(s.shape), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))
    if weights.shapes(layout) != want:
        raise SystemExit(f"chipbench: the reference's parameter layout for "
                         f"{cell.config['name']} differs from the program's")
    plan, qparams = served_params(
        layout, weights.make(layout, cell.config["weights"], seed),
        cell.config)
    serving = cell.config["serving"]
    return ServeEngine.from_quantised(
        cfg, qparams, plan, batch_slots=cell.mix["slots"],
        kv_len=serving["kv_len"], prefill_chunk=serving["prefill_chunk"],
        dense_fallback=False)


def step_batches(B: int, chunk: int) -> dict:
    """The step variants serving uses: prefill with the admission reset,
    prefill, decode."""
    import jax.numpy as jnp
    prefill = {"tokens": jnp.zeros((B, chunk), jnp.int32),
               "t_valid": jnp.full((B,), chunk, jnp.int32)}
    return {"prefill+reset": {**prefill, "reset": jnp.ones((B,), bool)},
            "prefill": prefill,
            "decode": {"tokens": jnp.zeros((B, 1), jnp.int32),
                       "t_valid": jnp.ones((B,), jnp.int32)}}


def warm(eng, kernels: List[str], on_tpu: bool) -> dict:
    """Compile every step variant, count the named kernels in each, and run
    each once through the engine's own jitted step and host copy. Returns
    the kernels each variant lacks."""
    from repro.kernels.ops import tpu_kernel_calls
    missing = {}
    for tag, batch in step_batches(eng.B, eng.prefill_chunk).items():
        compiled = eng._step.lower(eng.params, eng._state, batch).compile()
        calls = tpu_kernel_calls(compiled.as_text())
        lack = [k for k in kernels if on_tpu and not calls.get(k)]
        if lack:
            missing[tag] = lack
        logits, _ = eng._step(eng.params, eng._state, batch)
        np.asarray(logits)
    return missing


# ------------------------------------------------------------------ window

@dataclass
class Rec:
    """One request as the client sees it."""
    rid: int
    prompt: List[int]
    max_new: int
    handle: object = None
    snap: dict = field(default_factory=dict)

    def snapshot(self):
        g = self.handle.generation      # None: still waiting for a slot
        self.snap = dict(
            rid=self.rid, prompt=self.prompt, max_new=self.max_new,
            n_tokens=len(g.tokens) if g else 0,
            tokens=list(g.tokens) if g else [], done=bool(g and g.done),
            failed=bool(g and (g.failed or g.truncated)))


class Slots:
    """Per-step accounting from the engine's per-slot positions: how many
    tokens each step processed and how many cache rows its slots held."""

    def __init__(self, eng):
        self.eng = eng
        self.gen = [None] * eng.B
        self.pos = [0] * eng.B
        self.prefill = eng.prefill_steps

    def after_step(self, t0: float, t1: float) -> dict:
        eng = self.eng
        valid = rows = qrows = 0
        for i in range(eng.B):
            g, p = eng._slots[i], int(eng._slot_pos[i])
            if self.gen[i] is not None and g is not self.gen[i]:
                valid, rows, qrows = valid + 1, rows + self.pos[i] + 1, \
                    qrows + self.pos[i] + 1     # finished on its last token
            if g is not None and p > 0:
                adv = p - self.pos[i] if g is self.gen[i] else p
                valid, rows, qrows = valid + adv, rows + p, qrows + adv * p
            self.gen[i], self.pos[i] = g, p
        prefill = eng.prefill_steps - self.prefill
        self.prefill = eng.prefill_steps
        return dict(t0=t0, t1=t1, T=eng.prefill_chunk if prefill else 1,
                    valid=valid, rows=rows, qrows=qrows, slots=eng.B)


def serve(eng, cell: Cell, seed: int, seconds: float, trace_dir=None):
    """Drive the cell's traffic through the scheduler for ``seconds``.
    Returns (records, per-step records, (open, close) of the window, the
    traced range of steps, compiles in the window, the scheduler)."""
    import jax
    from jax._src import monitoring
    from repro.serve.scheduler import Scheduler

    compiles = []

    def count(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    mix = cell.mix
    sched = Scheduler(eng)
    plan = traffic.plan(mix, seed, cell.config["model"]["vocab"])
    recs: List[Rec] = []
    finished: list = []
    slots = Slots(eng)
    steps: List[dict] = []
    span = jax.profiler.TraceAnnotation
    traced, tracing = None, False
    gc.collect()
    monitoring.register_event_duration_secs_listener(count)
    t0 = time.monotonic()
    t_end = t0 + seconds

    def submit(p: traffic.Planned):
        r = Rec(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
        r.handle = sched.submit(p.prompt, max_new_tokens=p.max_new, rid=p.rid)
        recs.append(r)

    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        if trace_dir and not tracing and now >= t_end - TRACE_SECONDS:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracing, traced = True, [len(steps), None]
        with span("chipbench.submit"):
            while sched.waiting < mix["waiting"]:
                submit(next(plan))
        ts = time.monotonic()
        with span("chipbench.step_once"):
            if eng.step_once(finished):
                steps.append(slots.after_step(ts, time.monotonic()))
    t1 = time.monotonic()
    monitoring.unregister_event_duration_listener(count)
    if tracing:
        traced[1] = len(steps)
        jax.profiler.stop_trace()
    for r in recs:
        r.snapshot()
    return recs, steps, (t0, t1), traced, len(compiles), sched


# --------------------------------------------------------------- the check

def sample(recs: List[dict], seed: int, n: int) -> List[dict]:
    """The requests whose served tokens are compared: the one with the most
    tokens, and ``n - 1`` others drawn from the seed, out of every request
    that the window finished."""
    served = [r for r in recs if r["done"] and r["n_tokens"] > 0]
    if not served:
        return []
    longest = max(served, key=lambda r: (r["n_tokens"], -r["rid"]))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(traffic.seed_words(seed, 2 ** 32))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[int(i)] for i in sorted(pick)]


def gap_fn(cell: Cell, control: bool = False):
    """A jitted (weights, tokens (L,), targets (L,)) → (gaps, margins), each
    (L,): at each position whose target is a served token, how far that
    token's reference logit lies below the reference's best, and how far
    the reference's second best does. With ``control``, the gap of the
    token that the reference computed in float8 puts first."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference
    ref, model = cell.ref, cell.config["model"]

    def fn(w, tokens, targets):
        logits = ref.forward(w, tokens, model, reference.exact)
        top2 = jax.lax.top_k(logits, 2)[0]
        if control:
            lo = ref.forward(w, tokens, model, reference.fp8)
            pick = lo.argmax(-1)
        else:
            pick = jnp.maximum(targets, 0)
        got = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
        served = targets >= 0
        return (jnp.where(served, top2[:, 0] - got, 0.0),
                jnp.where(served, top2[:, 0] - top2[:, 1], jnp.nan))

    return jax.jit(fn)


def sequences(picked: List[dict], length: int):
    """Each picked request as (tokens, targets), padded to ``length``: the
    prompt and the served tokens but the last as input, and at the position
    that produced each served token, that token as the target."""
    out = []
    for r in picked:
        toks = list(r["prompt"]) + r["tokens"][:-1]
        P = len(r["prompt"])
        tgt = [-1] * (P - 1) + r["tokens"]
        pad = length - len(toks)
        out.append((np.asarray(toks + [0] * pad, np.int32),
                    np.asarray(tgt + [-1] * pad, np.int32)))
    return out


def widest_gap(cell: Cell, seed: int, picked, control: bool = False):
    """The reference over the picked requests, on weights made anew from
    the seed: (widest gap, served positions compared, median margin of the
    reference's best over its second best at those positions)."""
    import jax
    if not picked:
        return float("nan"), 0, float("nan")
    w = weights.make_dense(cell.ref.layout(cell.config["model"]),
                           cell.config["weights"], seed)
    fn = gap_fn(cell, control)
    worst, n, margins = 0.0, 0, []
    with jax.default_matmul_precision("highest"):
        for toks, tgt in sequences(picked, cell.config["serving"]["kv_len"]):
            g, m = (np.asarray(a) for a in fn(w, toks, tgt))
            worst = max(worst, float(g.max()))
            n += int((tgt >= 0).sum())
            margins.append(m[tgt >= 0])
    return worst, n, float(np.median(np.concatenate(margins)))


# ------------------------------------------------------------------ a run

def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, out_dir: Path, control: bool = False) -> dict:
    """Everything after the device check. Returns the result line. With
    ``control``, the control takes the program's place in the check: the
    gap compared is that of the token the reference computed in float8
    puts first, and the line also gives the program's own reading
    (``readings``). The benchmark's runs never set it."""
    from chipbench.peaks import peaks

    on_tpu = devices[0].platform == "tpu"
    t = time.monotonic()
    eng = build_engine(cell, seed)
    t_built = time.monotonic()
    missing = warm(eng, cell.config["kernels"], on_tpu)
    print(f"chipbench setup: start {t - t_start:.3f} s, engine "
          f"{t_built - t:.3f} s, step variants {time.monotonic() - t_built:.3f}"
          f" s", file=sys.stderr)
    trace_dir = out_dir / "trace" if trace else None
    window_start = time.monotonic()
    setup_s = window_start - t_start
    recs, steps, (t0, t1), traced, compiles, sched = serve(
        eng, cell, seed, seconds, trace_dir)
    mem = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    degraded = eng.degraded
    del eng, sched
    gc.collect()

    snaps = [r.snap for r in recs]
    ctx = SimpleNamespace(
        cell=cell, config=cell.config, model=cell.config["model"],
        mix=cell.mix, ref=cell.ref, setup_s=setup_s, t0=t0, t1=t1,
        window_s=t1 - t0, requests=snaps, steps=steps,
        peaks=peaks(devices[0].device_kind) if on_tpu else None, trace=None)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {}
    if trace:
        ctx.trace = read_trace(trace_dir, steps[traced[0]:traced[1]])
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown
    metrics = {}
    for m in (cell.per_layer if trace else cell.e2e):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    picked = sample(snaps, seed, cell.mix["check_requests"])
    gap, positions, _ = widest_gap(cell, seed, picked)
    if control:
        result["readings"] = {"program_gap": gap}
        gap, _, _ = widest_gap(cell, seed, picked, control=True)
        result["readings"]["control_gap"] = gap
    limit = cell.config["correct"]["widest_gap_limit"]
    failed = sum(r["failed"] for r in snaps)
    checks = {
        "widest_gap": [gap, limit],
        "compared_tokens": [positions, 1],
        "failed_requests": [failed, 0],
        "degraded": [int(degraded), 0],
        "steps_lacking_kernels": [len(missing), 0],
    }
    correct = (gap <= limit and positions >= 1 and failed == 0
               and not degraded and not missing)
    print(f"chipbench compiles_in_window {compiles}", file=sys.stderr)
    if missing:
        print(f"chipbench kernels missing {missing}", file=sys.stderr)
    print(f"chipbench compared {len(picked)} requests, "
          f"{len({t for r in picked for t in r['tokens']})} distinct served "
          f"tokens", file=sys.stderr)
    for name, (value, lim) in checks.items():
        rel = ">=" if name == "compared_tokens" else "<="
        print(f"chipbench check {name} {value} limit {rel} {lim}",
              file=sys.stderr)
    return {"correct": bool(correct), "attempted": len(recs),
            "failed": failed, "metrics": metrics, "device": device,
            **result, "checks": checks}


def read_trace(trace_dir: Path, steps: List[dict]) -> SimpleNamespace:
    """The traced window: device events, harness spans, busy time averaged
    over the devices, and the breakdown for the result line."""
    data = tr.load(tr.find_xplane(trace_dir))
    spans = data["spans"]
    lo = min(s for _, s, _ in spans)
    hi = max(s + d for _, s, d in spans)
    planes = sorted(data["devices"])
    ops = data["devices"][planes[0]] if planes else []
    busy = [tr.busy_ns(data["devices"][p], lo, hi) for p in planes] or [0.0]
    return SimpleNamespace(
        ops=ops, spans=spans, lo=lo, hi=hi, steps=steps,
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
        breakdown={"device_ops": tr.top_ops(ops, lo, hi),
                   "idle_gaps": tr.longest_gaps(ops, spans, lo, hi)})
