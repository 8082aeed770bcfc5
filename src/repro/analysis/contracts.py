"""Registry contract verifier: every ``ModelFamily`` × small config.

The dynamic invariants the serving stack enforces at runtime — packed
coverage through ``pack_layouts`` (PR 3), grouped decode-cache geometry
(PR 5), the ragged protocol (PR 4) — are all *declarations* a family
makes at registration. This module checks the declarations against the
family's actual callables **abstractly** (shape-level only, zero FLOPs):

* ``pack_layouts`` paths exist in the ``param_specs`` tree and their
  ``(n_lead, n_contract)`` subscripts are consistent with the declared
  parameter rank (at least one output dim must remain for the scale
  block to tile);
* ``decode_state_specs`` / ``cache_spec`` / ``CacheSpec.state_keys``
  agree: every grouped KV entry the cache geometry owns exists in the
  decode-state tree with the identical shape/dtype, and ``pos`` is the
  per-slot ``(B,) int32`` the ragged protocol requires;
* ``supports_ragged`` matches what ``jax.eval_shape`` on ``decode_step``
  actually accepts: a ``(B, T)`` chunk with ``t_valid`` + ``reset`` (and
  the plain ``T=1`` decode call) must trace, return ``(B, T, ·)`` logits,
  and hand back a state tree of the identical structure/shapes — the
  fixed-point property the engine's step loop relies on.

The default matrix pairs every registered family with every assigned
architecture's ``smoke()`` config (``repro.configs.ARCHS``) — all six
serving-bench family tags and then some — so a new family or config
inherits verification by existing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from .lint import Finding


@dataclass(frozen=True)
class ContractReport:
    tag: str
    family: str
    findings: Tuple[Finding, ...]

    @property
    def ok(self) -> bool:
        return not self.findings


def _leaf_shapes(tree) -> dict:
    """{leaf path: (shape, dtype)} of a state tree (entries may be lists
    of per-layer arrays)."""
    import jax
    return {jax.tree_util.keystr(path): (tuple(v.shape), str(v.dtype))
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def default_matrix() -> List[Tuple[str, object]]:
    """(tag, smoke config) for every assigned architecture."""
    from repro import configs
    return [(arch_id, configs.get_config(arch_id, "smoke"))
            for arch_id in sorted(configs.ARCHS)]


def verify_family(tag: str, cfg, *, batch: int = 2, kv_len: int = 24,
                  slack: int = 4, chunk: int = 4) -> ContractReport:
    """Verify one (tag, config) pair; abstract eval only."""
    import jax
    import jax.numpy as jnp
    from repro.models.api import ParamSpec, get_family, specs_to_sds

    fam = get_family(cfg.family)
    path = f"contracts:{tag}"
    findings: List[Finding] = []

    def fail(msg: str, hint: str = ""):
        findings.append(Finding(path, 0, "contract", msg, hint))

    # ---- pack_layouts paths + subscript consistency ----------------------
    specs = fam.param_specs(cfg)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    by_path = {jax.tree_util.keystr(p): s for p, s in flat}
    layouts = fam.pack_layouts(cfg)
    for lpath, layout in layouts.items():
        if lpath not in by_path:
            fail(f"pack_layouts path {lpath} not in the param tree "
                 f"(family {fam.name!r}); known leaves e.g. "
                 f"{sorted(by_path)[:3]}...",
                 "declare the layout against an existing param path")
            continue
        try:
            n_lead, n_contract = layout
        except (TypeError, ValueError):
            fail(f"pack_layouts[{lpath}] = {layout!r} is not an "
                 "(n_lead, n_contract) pair")
            continue
        spec = by_path[lpath]
        if n_lead < 0 or n_contract < 1:
            fail(f"pack_layouts[{lpath}] = {layout!r}: need n_lead >= 0 "
                 "and n_contract >= 1")
        elif len(spec.shape) < n_lead + n_contract + 1:
            fail(f"pack_layouts[{lpath}] = {layout!r} inconsistent with "
                 f"param rank {len(spec.shape)} (shape {spec.shape}): no "
                 "output dim remains for the scale block to tile")

    # ---- decode_state_specs / cache_spec / state_keys agreement ----------
    if fam.decode_state_specs is None:
        return ContractReport(tag, fam.name, tuple(findings))
    dss = fam.decode_state_specs(cfg, batch, kv_len, slack, True)
    pos = dss.get("pos") if isinstance(dss, dict) else None
    if pos is None or tuple(pos.shape) != (batch,) or pos.dtype != "int32":
        fail(f"decode_state_specs must declare per-slot 'pos' as "
             f"((batch,), int32); got {pos and (pos.shape, pos.dtype)}",
             "the ragged protocol keys on state['pos']: (B,) int32")
    if fam.cache_spec is not None:
        cs = fam.cache_spec(cfg, batch, kv_len, slack, True)
        cache_specs = cs.state_specs()
        for key in cs.state_keys:
            if key not in dss:
                fail(f"cache_spec owns state key {key!r} that "
                     "decode_state_specs does not declare",
                     "grouped k{g}/v{g} entries must ride the state tree")
                continue
            want, got = cache_specs[key], dss[key]
            if tuple(want.shape) != tuple(got.shape) \
                    or want.dtype != got.dtype:
                fail(f"state key {key!r}: cache_spec declares "
                     f"{want.shape}/{want.dtype} but decode_state_specs "
                     f"declares {got.shape}/{got.dtype}")

    # ---- supports_ragged vs what decode_step actually accepts ------------
    if fam.decode_step is None:
        if fam.supports_ragged:
            fail("supports_ragged=True but decode_step is None")
        return ContractReport(tag, fam.name, tuple(findings))
    params_sds = specs_to_sds(specs)
    state_sds = specs_to_sds(dss)
    i32 = jnp.dtype("int32")

    def trace(T, ragged):
        b = {"tokens": jax.ShapeDtypeStruct((batch, T), i32)}
        if ragged:
            b["t_valid"] = jax.ShapeDtypeStruct((batch,), i32)
            b["reset"] = jax.ShapeDtypeStruct((batch,), jnp.dtype(bool))
        return jax.eval_shape(
            lambda p, s, bb: fam.decode_step(p, s, bb, cfg),
            params_sds, state_sds, b)

    calls = ([(chunk, True), (1, False)] if fam.supports_ragged
             else [(1, False)])
    for T, ragged in calls:
        kind = (f"ragged (B, {T}) chunk + t_valid/reset" if ragged
                else "plain T=1 decode")
        try:
            logits, new_state = trace(T, ragged)
        except Exception as e:  # noqa: BLE001 — report, never crash
            fail(f"decode_step rejects the {kind} call the "
                 f"supports_ragged={fam.supports_ragged} declaration "
                 f"promises: {type(e).__name__}: {e}",
                 "the engine's jitted step issues exactly this shape")
            continue
        if tuple(logits.shape[:2]) != (batch, T):
            fail(f"decode_step {kind}: logits shaped {logits.shape}, "
                 f"expected leading ({batch}, {T})")
        in_tree = _leaf_shapes(state_sds)
        out_tree = _leaf_shapes(new_state) \
            if isinstance(new_state, dict) else None
        if out_tree != in_tree:
            only_in = sorted(set(in_tree) - set(out_tree or {}))
            only_out = sorted(set(out_tree or {}) - set(in_tree))
            diff = {k: (in_tree[k], (out_tree or {}).get(k))
                    for k in in_tree if k in (out_tree or {})
                    and (out_tree or {})[k] != in_tree[k]}
            fail(f"decode_step {kind}: state is not a fixed point of the "
                 f"declared specs (dropped={only_in}, added={only_out}, "
                 f"reshaped={diff})",
                 "the engine feeds state back verbatim every step")

    # ---- quantised cache formats (PR 10) ---------------------------------
    # re-verify the same declarations with a quantised kv_format: the cache
    # geometry must grow uint8 code + float32 scale entries per group, the
    # decode-state tree must carry them identically, and decode_step must
    # trace (and fix-point) against the quantised state.
    if fam.cache_spec is not None and fam.supports_ragged:
        qfmt = "q4" if cfg.hd % 2 == 0 else "q8"
        qcfg = cfg.replace(kv_format=qfmt)
        qcs = fam.cache_spec(qcfg, batch, kv_len, slack, True)
        qspecs = qcs.state_specs()
        for g in qcs.groups:
            if not g.quantised:
                fail(f"cache_spec ignores cfg.kv_format={qfmt!r}: group "
                     f"{g.index} stayed {g.fmt!r}",
                     "pass formats=cfg.kv_format to build_cache_spec")
                continue
            code, scale = qspecs[g.k_key], qspecs[g.k_scale_key]
            if code.dtype != "uint8":
                fail(f"quantised group {g.index}: codes declared "
                     f"{code.dtype}, expected uint8")
            if scale.dtype != "float32" or tuple(scale.shape)[-1] != 1:
                fail(f"quantised group {g.index}: scales declared "
                     f"{scale.shape}/{scale.dtype}, expected per-(token, "
                     "head) float32 with trailing dim 1")
        qdss = fam.decode_state_specs(qcfg, batch, kv_len, slack, True)
        for key in qcs.state_keys:
            if key not in qdss:
                fail(f"quantised cache key {key!r} missing from "
                     f"decode_state_specs under kv_format={qfmt!r}",
                     "codes + scales must ride the state tree")
                continue
            want, got = qspecs[key], qdss[key]
            if tuple(want.shape) != tuple(got.shape) \
                    or want.dtype != got.dtype:
                fail(f"quantised state key {key!r}: cache_spec declares "
                     f"{want.shape}/{want.dtype} but decode_state_specs "
                     f"declares {got.shape}/{got.dtype}")
        qstate_sds = specs_to_sds(qdss)
        qb = {"tokens": jax.ShapeDtypeStruct((batch, chunk), i32),
              "t_valid": jax.ShapeDtypeStruct((batch,), i32),
              "reset": jax.ShapeDtypeStruct((batch,), jnp.dtype(bool))}
        try:
            _, qnew = jax.eval_shape(
                lambda p, s, bb: fam.decode_step(p, s, bb, qcfg),
                params_sds, qstate_sds, qb)
        except Exception as e:  # noqa: BLE001 — report, never crash
            fail(f"decode_step rejects the ragged chunk under "
                 f"kv_format={qfmt!r}: {type(e).__name__}: {e}",
                 "the quantised cache must serve through the same step")
        else:
            q_in = _leaf_shapes(qstate_sds)
            q_out = _leaf_shapes(qnew) if isinstance(qnew, dict) else None
            if q_out != q_in:
                fail(f"decode_step under kv_format={qfmt!r}: state is not "
                     "a fixed point of the quantised specs",
                     "codes/scales entries must round-trip the step")
    return ContractReport(tag, fam.name, tuple(findings))


def verify_all(matrix: Optional[Sequence[Tuple[str, object]]] = None
               ) -> List[ContractReport]:
    """Verify the full matrix (default: every assigned arch's smoke
    config). Every registered family must be covered — a family that no
    config exercises is itself a contract violation."""
    from repro.models import api as mapi
    mx = list(matrix) if matrix is not None else default_matrix()
    reports = [verify_family(tag, cfg) for tag, cfg in mx]
    if matrix is None:
        mapi.get_family("transformer")  # force side-effect registration
        covered = {r.family for r in reports}
        missing = sorted(set(mapi._FAMILIES) - covered)
        if missing:
            reports.append(ContractReport(
                "registry", ",".join(missing), (Finding(
                    "contracts:registry", 0, "contract",
                    f"registered families {missing} are exercised by no "
                    "assigned config — add a smoke config or retire them",
                    "every ModelFamily must be reachable from "
                    "repro.configs.ARCHS"),)))
    return reports


__all__ = ["ContractReport", "default_matrix", "verify_family",
           "verify_all"]
