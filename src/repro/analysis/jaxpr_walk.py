"""Generic jaxpr walking — THE shared iteration layer for every structural
pass over a built step (cost model, overlap counters, the wire auditor).

Promoted out of ``benchmarks/jaxpr_cost.py`` (PR 8) so src-side analyses
don't import a benchmark module: the benchmarks now re-export from here.
Everything in this module is structural only — no cost semantics, no rule
semantics; those live in the consumers (:mod:`benchmarks.jaxpr_cost`,
:mod:`repro.analysis.wire_audit`, :mod:`repro.analysis.schedule`,
:mod:`repro.analysis.traffic`).

The cross-scope dataflow graph (PR 9, promoted from ``wire_audit`` where it
served only the observed-clip rule) also lives here: ``build_graph`` records
per-var defining eqns, consuming eqns AND equality links across scope
boundaries (call in/outvars, scan consts/carries/xs/ys, cond branches, while
carries), so both backward reachability (what feeds a value) and forward
reachability (what a value feeds) are one traversal each — the primitives the
schedule analyzer's overlap-eligibility classification is built from.

Fixes folded in with the promotion (both were latent walker bugs):

  * ``COLLECTIVES`` includes ``pmean`` — a backend/JAX version that emits a
    first-class pmean primitive would previously count zero collective bytes
    in the roofline table (current CPU JAX lowers ``lax.pmean`` to
    psum+div, so the entry is future-proofing, not a behavior change here);
  * ``iter_eqns`` scans the REMAINING params of a ``cond`` eqn after its
    branches instead of ``continue``-ing — a cond carrying another sub-jaxpr
    param would previously have that subtree silently skipped.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Tuple

import numpy as np

__all__ = [
    "COLLECTIVES",
    "CALL_PRIMS",
    "iter_eqns",
    "iter_eqns_scaled",
    "eqn_subjaxprs",
    "eqn_axes",
    "collective_eqns",
    "aval_size_bytes",
    "aval_nelem",
    "DataflowGraph",
    "build_graph",
    "backward_eqns",
    "forward_eqns",
]

# collective primitive name -> communication kind. The auditor and the cost
# model both key off this table; a primitive missing here is invisible to
# every structural pass, so additions belong HERE, not in the consumers.
COLLECTIVES = {
    "psum": "all-reduce",
    # what lax.psum binds inside a check_vma=True shard_map
    "psum_invariant": "all-reduce",
    "pmean": "all-reduce",  # only present on JAX builds with a pmean prim
    "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "psum_scatter": "reduce-scatter",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
}

# collectives whose payload is combined across devices (vs merely moved /
# concatenated) — the surface the floatless-wire rule audits. A ppermute hop
# is included: on the ring route it carries in-flight partial SUMS.
REDUCING_COLLECTIVES = frozenset(
    {"psum", "psum_invariant", "pmean", "pmax", "pmin", "reduce_scatter",
     "psum_scatter", "ppermute"}
)

CALL_PRIMS = ("jit", "closed_call", "core_call", "custom_jvp_call",
              "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "remat2",
              "checkpoint", "custom_lin")


def _as_jaxpr(v):
    """ClosedJaxpr | Jaxpr -> Jaxpr."""
    return v.jaxpr if hasattr(v, "jaxpr") else v


def eqn_subjaxprs(eqn) -> Iterator:
    """Every sub-jaxpr held by ``eqn.params``, each exactly once.

    Scans ALL params: the ``branches`` tuple of a cond AND any ``*jaxpr``
    param the same eqn carries (the old walker ``continue``-d after the
    branches, skipping sibling sub-jaxpr params)."""
    for k, v in eqn.params.items():
        if k == "branches":
            for b in v:
                yield _as_jaxpr(b)
        elif k.endswith("jaxpr") and (hasattr(v, "eqns") or hasattr(v, "jaxpr")):
            yield _as_jaxpr(v)


def iter_eqns(jaxpr) -> Iterator:
    """Yield every eqn in `jaxpr` and all sub-jaxprs, each ONCE — cond
    branches and while cond/body included, scan bodies NOT multiplied by
    trip count. Structural-counting walks (collective counts, primitive
    presence, the wire audit) build on this; :func:`benchmarks.jaxpr_cost
    .jaxpr_cost` keeps its own recursion because byte/FLOP accounting needs
    scan-length scaling and worst-cond-branch semantics that a flat
    iteration cannot express."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn_subjaxprs(eqn):
            yield from iter_eqns(sub)


def eqn_axes(eqn) -> Tuple[str, ...]:
    """The mesh/vmap axis names a collective eqn communicates over."""
    p = eqn.params
    for k in ("axes", "axis_name", "axis_names"):
        if k in p:
            a = p[k]
            if isinstance(a, (tuple, list, frozenset, set)):
                return tuple(sorted(str(x) for x in a))
            return (str(a),)
    return ("?",)


def collective_eqns(jaxpr) -> Iterator[tuple]:
    """Yield ``(eqn, kind, axes)`` for every collective in the whole tree."""
    for eqn in iter_eqns(jaxpr):
        kind = COLLECTIVES.get(eqn.primitive.name)
        if kind is not None:
            yield eqn, kind, eqn_axes(eqn)


def iter_eqns_scaled(jaxpr, scale: int = 1) -> Iterator[Tuple[object, int]]:
    """Yield ``(eqn, multiplicity)`` over the whole tree: scan bodies are
    multiplied by their trip count (nested scans compound), while bodies
    count once (no unbounded whiles in this codebase), cond branches each
    count once (branch selection is dynamic; a structural pass sees both).
    The flat-count sibling of :func:`iter_eqns` for passes that need
    execution multiplicity (FLOP totals, scan-aware byte accounting)."""
    for eqn in jaxpr.eqns:
        yield eqn, scale
        name = eqn.primitive.name
        k = int(eqn.params["length"]) if name == "scan" else 1
        for sub in eqn_subjaxprs(eqn):
            yield from iter_eqns_scaled(sub, scale * k)


def aval_size_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def aval_nelem(aval) -> int:
    try:
        return int(np.prod(aval.shape))
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# cross-scope dataflow graph (promoted from wire_audit in PR 9)
# ---------------------------------------------------------------------------
def is_var(a) -> bool:
    """True for jaxpr Vars (Literals carry a ``val``)."""
    return not hasattr(a, "val")


@dataclasses.dataclass
class DataflowGraph:
    """Value-flow indices over one closed jaxpr and every nested scope.

    ``defs``:  id(var) -> defining eqn;
    ``uses``:  id(var) -> eqns consuming it (within its own scope);
    ``links``: id(var) -> vars EQUAL to it across a scope boundary (call
    in/outvars, scan consts/carries/xs/ys, cond branches, while carries);
    ``opaque``: id(call eqn) -> ids of every eqn inside its sub-jaxprs, for
    call eqns whose body links were withheld (see ``shared_bodies`` below).

    Links are value-equality edges, so reachability may traverse them in
    either direction — that is what lets one backward or forward sweep cross
    shard_map / jit / scan bodies without modeling each call convention."""

    defs: Dict[int, object]
    uses: Dict[int, List[object]]
    links: Dict[int, List[object]]
    opaque: Dict[int, Tuple[int, ...]] = dataclasses.field(
        default_factory=dict
    )


def _count_call_sites(jaxpr, counts: Dict[int, int]) -> None:
    for eqn in jaxpr.eqns:
        for sub in eqn_subjaxprs(eqn):
            counts[id(sub)] = counts.get(id(sub), 0) + 1
            _count_call_sites(sub, counts)


def build_graph(closed_jaxpr, *, shared_bodies: str = "link") -> DataflowGraph:
    """Build the :class:`DataflowGraph` for a (Closed)Jaxpr.

    ``shared_bodies`` decides what to do with a sub-jaxpr OBJECT that is
    shared by several call sites (jax caches jaxprs, so e.g. one tiny
    ``jit[name=clip]`` body serves every microbatch's call):

      * ``"link"`` (default): link body vars to EVERY call site. Boundary
        links become a hub joining all call sites, so reachability is merged
        across them — maximally conservative, fine for existence checks
        (wire_audit's observed-clip rule wants "is SOME clamp upstream").
      * ``"opaque"``: withhold boundary links for multi-site bodies and
        record the call eqn in ``opaque`` instead; reachability walks treat
        the call atomically (its inputs feed all its outputs) and
        wholesale-include the body's eqns when the call itself is reached.
        Per-call-site precise — required by the schedule analyzer, where the
        "link" hub would order every microbatch against every collective and
        falsely kill all concurrency.
    """
    if shared_bodies not in ("link", "opaque"):
        raise ValueError(
            f"shared_bodies must be 'link' or 'opaque', got {shared_bodies!r}"
        )
    g = DataflowGraph(defs={}, uses={}, links={}, opaque={})
    site_counts: Dict[int, int] = {}
    if shared_bodies == "opaque":
        _count_call_sites(_as_jaxpr(closed_jaxpr), site_counts)

    def link(a, b):
        if is_var(a) and is_var(b):
            g.links.setdefault(id(a), []).append(b)
            g.links.setdefault(id(b), []).append(a)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                g.defs[id(ov)] = eqn
            for iv_ in eqn.invars:
                if is_var(iv_):
                    g.uses.setdefault(id(iv_), []).append(eqn)
            name = eqn.primitive.name
            p = eqn.params
            if any(site_counts.get(id(sub), 0) > 1
                   for sub in eqn_subjaxprs(eqn)):
                g.opaque[id(eqn)] = tuple(
                    id(e)
                    for sub in eqn_subjaxprs(eqn)
                    for e in iter_eqns(sub)
                )
                for sub in eqn_subjaxprs(eqn):
                    walk(sub)
                continue
            if name == "scan":
                body = _as_jaxpr(p["jaxpr"])
                nc, nk = p["num_consts"], p["num_carry"]
                for i in range(nc):
                    link(body.invars[i], eqn.invars[i])
                for j in range(nk):
                    link(body.invars[nc + j], eqn.invars[nc + j])  # init
                    link(body.invars[nc + j], body.outvars[j])  # loop
                    link(eqn.outvars[j], body.outvars[j])
                for k in range(nc + nk, len(body.invars)):
                    link(body.invars[k], eqn.invars[k])
                for j in range(nk, len(body.outvars)):
                    link(eqn.outvars[j], body.outvars[j])
            elif name == "while":
                body = p["body_jaxpr"].jaxpr
                cn, bn = p["cond_nconsts"], p["body_nconsts"]
                carry = eqn.invars[cn + bn:]
                for i in range(bn):
                    link(body.invars[i], eqn.invars[cn + i])
                for j, c in enumerate(carry):
                    link(body.invars[bn + j], c)
                    link(body.invars[bn + j], body.outvars[j])
                    link(eqn.outvars[j], body.outvars[j])
            elif name == "cond":
                for br in p["branches"]:
                    sub = _as_jaxpr(br)
                    for bi, xi in zip(sub.invars, eqn.invars[1:]):
                        link(bi, xi)
                    for bo, xo in zip(sub.outvars, eqn.outvars):
                        link(xo, bo)
            else:
                for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    if k in p:
                        sub = _as_jaxpr(p[k])
                        if (len(sub.invars) == len(eqn.invars)
                                and len(sub.outvars) == len(eqn.outvars)):
                            for bi, xi in zip(sub.invars, eqn.invars):
                                link(bi, xi)
                            for bo, xo in zip(sub.outvars, eqn.outvars):
                                link(xo, bo)
                        break
            for sub in eqn_subjaxprs(eqn):
                walk(sub)

    walk(_as_jaxpr(closed_jaxpr))
    return g


def backward_eqns(roots, graph: DataflowGraph) -> set:
    """ids of every eqn whose output can flow into any root var. Reaching an
    ``opaque`` call eqn wholesale-includes its body's eqns (everything inside
    executes before the call's outputs exist)."""
    seen_vars: set = set()
    hit: set = set()
    stack = [r for r in roots if is_var(r)]
    while stack:
        v = stack.pop()
        if id(v) in seen_vars:
            continue
        seen_vars.add(id(v))
        eqn = graph.defs.get(id(v))
        if eqn is not None and id(eqn) not in hit:
            hit.add(id(eqn))
            hit.update(graph.opaque.get(id(eqn), ()))
            stack.extend(a for a in eqn.invars if is_var(a))
        stack.extend(graph.links.get(id(v), ()))
    return hit


def forward_eqns(roots, graph: DataflowGraph) -> set:
    """ids of every eqn any root var can flow into (the consumer closure —
    the dual of :func:`backward_eqns`, via ``uses`` instead of ``defs``).
    Reaching an ``opaque`` call eqn wholesale-includes its body's eqns
    (everything inside executes after the call's inputs arrive)."""
    seen_vars: set = set()
    hit: set = set()
    stack = [r for r in roots if is_var(r)]
    while stack:
        v = stack.pop()
        if id(v) in seen_vars:
            continue
        seen_vars.add(id(v))
        for eqn in graph.uses.get(id(v), ()):
            if id(eqn) not in hit:
                hit.add(id(eqn))
                hit.update(graph.opaque.get(id(eqn), ()))
                stack.extend(ov for ov in eqn.outvars if is_var(ov))
        stack.extend(graph.links.get(id(v), ()))
    return hit
