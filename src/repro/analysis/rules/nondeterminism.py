"""nondeterminism: hidden-global randomness / wall clock in step paths.

Serving is drilled on bit-determinism — replays through the scheduler
give identical tokens and step counts, packed serving matches the dense
path token for token, sampling seeds per (rid, token-index). Unseeded
global-state randomness (legacy ``np.random.*`` samplers, stdlib
``random``) or wall-clock reads (``time.time``) inside a step/serve path
silently break replay without failing any test. Seed explicitly through
``np.random.default_rng(seed)`` (or an ``np.random.Generator`` threaded
from the caller); use ``time.monotonic()`` for latency metrics — it is
allowed everywhere because it only feeds accounting, never compute.

Scope: the legacy-``np.random``/stdlib-``random`` checks apply to every
linted file; the ``time.time`` check applies only to step/serve paths
(``src/repro/serve``, ``src/repro/models``, ``src/repro/kernels``) —
training loops and launch scripts legitimately report wall-clock
throughput. Files outside ``src/repro`` (fixtures, explicit paths) get
the full rule.
"""
from __future__ import annotations

import ast

from . import dotted_name, in_repo_src

_NP_LEGACY = {
    "rand", "randn", "randint", "random", "random_sample", "ranf", "sample",
    "choice", "shuffle", "permutation", "normal", "uniform",
    "standard_normal", "poisson", "exponential", "beta", "binomial",
    "gamma", "gumbel", "laplace", "logistic", "lognormal", "seed",
}
_STDLIB_RANDOM = {"random", "randint", "choice", "shuffle", "uniform",
                  "randrange", "sample", "seed", "gauss", "betavariate"}


class NondeterminismRule:
    rule_id = "nondeterminism"
    hint = ("seed via np.random.default_rng(seed); use time.monotonic() "
            "for timing metrics")

    def check(self, tree, src, path):
        p = path.replace("\\", "/")
        step_path = (not in_repo_src(p)
                     or "src/repro/serve" in p or "src/repro/models" in p
                     or "src/repro/kernels" in p)
        findings = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in ("time.time", "time.time_ns") and step_path:
                findings.append((node.lineno, (
                    f"{name}() in a step/serve path — wall clock is "
                    "nondeterministic across replays")))
                continue
            parts = name.split(".")
            if (len(parts) == 3 and parts[0] in ("np", "numpy")
                    and parts[1] == "random" and parts[2] in _NP_LEGACY):
                findings.append((node.lineno, (
                    f"unseeded legacy {name}() draws from (or reseeds) "
                    "numpy's hidden global RNG state")))
            elif (len(parts) == 2 and parts[0] == "random"
                    and parts[1] in _STDLIB_RANDOM):
                findings.append((node.lineno, (
                    f"stdlib {name}() draws from hidden global RNG "
                    "state")))
        return findings
