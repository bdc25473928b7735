"""The floatless-wire auditor: static proof of the integer wire on a jaxpr.

Rules (W = wire; violations carry these ids):

  W001  floatless dp wire — no floating-dtype operand on a REDUCING
        collective over the data-parallel axes. Scalar loss/metric
        reductions (≤ ``scalar_allowance`` elements) are allowed; ZeRO-1's
        bf16 param all-gathers are a gather, not a reduce, and are exempt.
        Integer GATHERS above the allowance are wire payload and must be
        declared: allowed only when the spec's codec transport is "gather"
        (TopKInt's idx/vals planes) or overlap is "ring" (the ring route
        finishes with an integer all-gather); otherwise they are flagged
        as undeclared wire traffic.
  W002  wire range safety — every integer operand of a reducing dp-axis
        collective is *provably bounded* by the forward interval pass, fits
        its transport lane after the n-worker sum, and the declared
        (kind, bits, n_workers, n_accum) chain proof
        (:func:`repro.analysis.intervals.wire_chain_proof`) holds — also
        for every clip bound OBSERVED in the jaxpr upstream of the wire
        (a clip looser than the declared §5.1 limit, e.g. a forgotten
        ``n_accum``, re-proves with the observed bound and fails).
  W003  fused-route image locality — with the packed codec the unpacked
        integer image must never materialize in HBM between the wire and
        the Pallas update kernel: every pallas_call consuming int32 at
        image size (rather than packed-word size) is flagged.

Suppression: a rule can be waived for one audit by passing
``suppress={"W00x": "justification"}`` — the justification string is
recorded in the report (empty justifications are rejected), mirroring the
lint-side ``# lint: allow(C00x) -- why`` escape hatch.

The auditor trusts the Pallas kernels' *internal* arithmetic (their
encode/pack parity with the jnp reference is pinned by tests/test_kernels
and tests/test_wire_pack); when ``spec.use_kernels`` is set, kernel
outputs get the declared stage bounds instead of TOP.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.analysis import jaxpr_walk as jw
from repro.analysis import intervals as iv
from repro.analysis.intervals import Interval, TOP

__all__ = [
    "RULES",
    "SCALAR_REDUCE_ALLOWANCE",
    "WireSpec",
    "Violation",
    "AuditReport",
    "WireAuditError",
    "audit_jaxpr",
    "audit_step",
    "spec_for_step",
]

RULES = {
    "W001": "no float operand on a reducing dp-axis collective; integer "
            "gathers only when the spec declares a gather-transport codec "
            "or the ring route (float gathers exempt)",
    "W002": "integer wire operands provably bounded; §5.1 guard-bit chain "
            "proof holds for declared AND jaxpr-observed clip bounds",
    "W003": "packed fused route: unpacked integer image never "
            "materializes in HBM between wire and Pallas kernel",
}

_LANE_MAX = {"int8": 127, "int16": 32767}

# W001's escape hatch for float reductions that are METRICS, not gradient
# payload: the loss mean, grad-norm/clip scalars, the stacked per-leaf
# ||·||² vector of _global_reduce_leaf_sq. 64 elements ≈ the largest leaf
# COUNT a shipped config stacks into one such vector, and is 4+ orders of
# magnitude below the smallest gradient leaf — so a float gradient can never
# hide under the allowance, while per-leaf diagnostics always fit. The
# 64/65 boundary is pinned by tests/test_analysis.py.
SCALAR_REDUCE_ALLOWANCE = 64


class WireAuditError(AssertionError):
    """Raised by ``AuditReport.raise_if_failed`` / ``verify='static'``."""


@dataclasses.dataclass(frozen=True)
class WireSpec:
    """The declared wire configuration one audit verifies against — the
    dp-axis tagging plus codec/pipelining facts ``build_train_step``
    attaches to its :class:`~repro.launch.step.StepArtifacts`."""

    dp_axes: Tuple[str, ...]
    axis_sizes: Dict[str, int]  # ALL mesh axes (collective scaling)
    n_workers: int
    n_accum: int = 1
    wire_kind: str = "dense"  # "dense" | "packed" | "topk"
    bits: int = 32
    use_kernels: bool = False
    fused: bool = False
    scalar_allowance: int = SCALAR_REDUCE_ALLOWANCE
    # transport declaration (PR 9) — what the traffic accountant and the
    # schedule analyzer prove the trace against. ``leaf_sizes`` is the
    # element count of each LOCAL param leaf (the integer image the codec
    # packs), in flatten order; ``overlap``/``bucket_words`` mirror the
    # CommCtx the step was built with. Empty leaf_sizes = unknown payload
    # (hand-built specs): the byte/count equality rules are skipped.
    leaf_sizes: Tuple[int, ...] = ()
    overlap: str = "off"
    bucket_words: int = 0
    # sparse/multi-plane declaration (PR 10) — ``wire_transport`` is the
    # codec's declared collective shape ("psum" | "gather"); ``topk_k`` is
    # the per-leaf selection size for kind "topk" (0 otherwise).
    wire_transport: str = "psum"
    topk_k: int = 0

    @property
    def lim(self) -> int:
        """Declared clip limit for this codec: the §5.1 n·M-divided bound
        for summing transports, the full int-range for gather kinds."""
        return iv.declared_clip_limit(
            self.wire_kind, self.n_workers * self.n_accum, self.bits
        )

    @property
    def dp_sizes(self) -> Tuple[int, ...]:
        return tuple(self.axis_sizes.get(a, 1) for a in self.dp_axes)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["axis_sizes"] = dict(d["axis_sizes"])
        return d


def _unwrap_wire(wf):
    """WireFormat | Logged wrapper -> the underlying concrete format."""
    while hasattr(wf, "inner"):
        wf = wf.inner
    return wf


def spec_for_step(layout, wf, *, n_accum: int = 1, fused: bool = False) -> WireSpec:
    """Build the audit spec from a resolved launch layout + wire format.

    Besides the codec facts, the spec declares the step's TRANSPORT: per-leaf
    integer-image sizes (from the layout's local param structs) and the
    overlap/bucketing mode from its CommCtx — everything the static byte
    accountant (:mod:`repro.analysis.traffic`) needs to reconstruct, without
    executing, exactly what the ``Logged`` codec would meter at trace time."""
    import math

    wf = _unwrap_wire(wf)
    leaf_sizes = tuple(
        int(math.prod(l.shape)) for l in _tree_leaves(layout.l_shapes)
    )
    ctx = getattr(layout, "ctx", None)
    return WireSpec(
        dp_axes=tuple(layout.dp),
        axis_sizes=dict(layout.mesh.shape),
        n_workers=layout.n_dp,
        n_accum=n_accum,
        wire_kind=str(wf.name),
        bits=int(wf.bits),
        use_kernels=bool(getattr(wf, "use_kernels", False)),
        fused=fused,
        leaf_sizes=leaf_sizes,
        overlap=getattr(ctx, "overlap", "off"),
        bucket_words=int(getattr(ctx, "bucket_words", 0)),
        wire_transport=str(getattr(wf, "transport", "psum")),
        topk_k=int(getattr(wf, "k", 0)),
    )


def _tree_leaves(tree):
    import jax  # deferred: the lint half of repro.analysis is jax-free

    return jax.tree.leaves(tree)


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    where: str  # primitive@axes dtype(shape) — or chain:<stage> for proofs
    message: str

    def __str__(self):
        return f"[{self.rule}] {self.where}: {self.message}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class AuditReport:
    spec: WireSpec
    proof: iv.ChainProof
    violations: Tuple[Violation, ...]
    suppressed: Tuple[Tuple[Violation, str], ...]
    stats: Dict[str, int]

    @property
    def ok(self) -> bool:
        return not self.violations

    def raise_if_failed(self):
        if not self.ok:
            lines = "\n".join(f"  {v}" for v in self.violations)
            raise WireAuditError(
                f"floatless-wire audit failed "
                f"({len(self.violations)} violation(s)):\n{lines}"
            )

    def to_dict(self) -> dict:
        return {
            "spec": self.spec.to_dict(),
            "proof": {
                "lim": self.proof.lim,
                "stages": {
                    k: [s.lo, s.hi] for k, s in self.proof.stages.items()
                },
            },
            "violations": [v.to_dict() for v in self.violations],
            "suppressed": [
                {**v.to_dict(), "justification": j} for v, j in self.suppressed
            ],
            "stats": dict(self.stats),
            "ok": self.ok,
        }


# --------------------------------------------------------------------------
# cross-scope dataflow graph — the generic defs/uses/links construction and
# plain backward reachability live in jaxpr_walk (promoted there in PR 9 so
# the schedule analyzer shares them); this module keeps only the WIRE-path
# restricted walk below.
# --------------------------------------------------------------------------
_is_var = jw.is_var

# Primitives a value may pass through between its §5.1 clip and the dp
# collective: rounding, scaling, lane casts, bit-packing, bucketing and the
# ring transport. The clip-attribution walk stops at anything else (matmuls,
# gathers, reductions), so data-path clips deep in the model — token-id
# clips, logit caps — are NOT mistaken for wire clips. schedule.py's P002
# round-trip rule keys off the same set: a cast is "on the wire path" iff
# this walk reaches it.
WIRE_PATH_PRIMS = frozenset({
    "convert_element_type", "bitcast_convert_type", "reshape",
    "broadcast_in_dim", "squeeze", "transpose", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "add", "sub", "mul",
    "neg", "max", "min", "clamp", "abs", "sign", "floor", "round", "rem",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "select_n", "stop_gradient",
    "optimization_barrier", "copy", "reshard", "pvary", "ppermute",
    "all_gather", "psum", "psum_invariant",
})


def backward_wire_eqns(roots, graph: jw.DataflowGraph) -> set:
    """Like :func:`jaxpr_walk.backward_eqns` but only walks THROUGH
    wire-path primitives; call/scan scopes are crossed via equality links
    (never by jumping a call eqn's invars, which would tunnel past its
    body)."""
    seen_vars: set = set()
    hit: set = set()
    stack = [r for r in roots if _is_var(r)]
    while stack:
        v = stack.pop()
        if id(v) in seen_vars:
            continue
        seen_vars.add(id(v))
        eqn = graph.defs.get(id(v))
        if eqn is not None and id(eqn) not in hit:
            hit.add(id(eqn))
            if (next(jw.eqn_subjaxprs(eqn), None) is None
                    and eqn.primitive.name in WIRE_PATH_PRIMS):
                stack.extend(a for a in eqn.invars if _is_var(a))
        stack.extend(graph.links.get(id(v), ()))
    return hit


# --------------------------------------------------------------------------
# the audit
# --------------------------------------------------------------------------
def _fmt_where(eqn, axes) -> str:
    a = eqn.invars[0].aval if eqn.invars else eqn.outvars[0].aval
    return (
        f"{eqn.primitive.name}@{','.join(axes)} "
        f"{a.dtype}{tuple(a.shape)}"
    )


def _pallas_override(spec: WireSpec, proof: iv.ChainProof):
    """Trusted-kernel transfer for pallas_call when the codec routes its
    hot stages through the Pallas kernels: integer outputs of an
    encode-style call (float in, int out) get the declared accumulator
    bound; word-producing calls (int in, int out) get the 32-bit word
    range (bounded, field-level safety comes from the chain proof)."""
    acc = proof.stages["accum"]
    word = Interval(-(2 ** 31), 2 ** 32 - 1)

    def run(eqn, ins):
        any_float_in = any(
            getattr(v.aval, "dtype", None) is not None
            and v.aval.dtype.kind == "f"
            for v in eqn.invars
        )
        outs = []
        for ov in eqn.outvars:
            if ov.aval.dtype.kind == "i":
                outs.append(acc if any_float_in else word)
            else:
                outs.append(TOP)
        return outs

    return run


def audit_jaxpr(
    closed_jaxpr,
    spec: WireSpec,
    *,
    suppress: Optional[Dict[str, str]] = None,
) -> AuditReport:
    """Statically verify the floatless-wire contract on a traced step."""
    suppress = dict(suppress or {})
    for rule, why in suppress.items():
        if rule not in RULES:
            raise ValueError(f"unknown rule {rule!r} in suppress")
        if not str(why).strip():
            raise ValueError(
                f"suppressing {rule} requires a non-empty justification"
            )

    violations: List[Violation] = []
    proof = iv.wire_chain_proof(
        spec.wire_kind, spec.bits, spec.n_workers, spec.n_accum
    )
    for check_id, msg in proof.violations:
        violations.append(Violation("W002", f"chain:{check_id}", msg))

    # ---- forward interval pass, observing every eqn -------------------
    obs: Dict[int, list] = {}
    order: List[int] = []

    def on_eqn(eqn, ins, outs):
        rec = obs.get(id(eqn))
        if rec is None:
            obs[id(eqn)] = [eqn, list(ins), list(outs)]
            order.append(id(eqn))
        else:  # an eqn replayed per scan iteration: union the observations
            rec[1] = [a.union(b) for a, b in zip(rec[1], ins)]
            rec[2] = [a.union(b) for a, b in zip(rec[2], outs)]

    overrides = (
        {"pallas_call": _pallas_override(spec, proof)}
        if spec.use_kernels
        else None
    )
    iv.eval_jaxpr_intervals(
        closed_jaxpr,
        axis_sizes=spec.axis_sizes,
        prim_overrides=overrides,
        on_eqn=on_eqn,
    )

    stats = {
        "eqns": len(order),
        "dp_collectives": 0,
        "int_wire_ops": 0,
        "scalar_float_reduces": 0,
        "clips_checked": 0,
        "pallas_calls": 0,
    }
    dp = set(spec.dp_axes)
    wire_roots: List = []  # int operands of reducing dp collectives

    for key in order:
        eqn, ins, _outs = obs[key]
        name = eqn.primitive.name
        if name == "pallas_call":
            stats["pallas_calls"] += 1
        if name not in jw.COLLECTIVES:
            continue
        axes = jw.eqn_axes(eqn)
        if not (set(axes) & dp):
            continue  # model/sp-axis collective: TP floats are by design
        stats["dp_collectives"] += 1
        if name not in jw.REDUCING_COLLECTIVES:
            # A non-reducing dp collective (all-gather) moves data without
            # combining it. Float gathers stay exempt — ZeRO-1's bf16 param
            # all-gathers are legitimate non-wire traffic. INTEGER gathers
            # above the scalar allowance ARE wire payload, though, and must
            # be declared: either the codec's transport is "gather"
            # (TopKInt's idx/vals planes) or the ring route's finishing
            # all_gather under overlap="ring". Declared gather operands
            # join wire_roots (so the observed-clip re-proof covers their
            # upstream clamps) but carry NO boundedness requirement —
            # nothing sums on a gather wire, two's-complement fields are
            # lossless, and the decode-side scatter-add bound is the chain
            # proof's image_sum check.
            gather_declared = (
                spec.wire_transport == "gather" or spec.overlap == "ring"
            )
            for operand, ival in zip(eqn.invars, ins):
                aval = getattr(operand, "aval", None)
                if aval is None or not hasattr(aval, "dtype"):
                    continue
                if aval.dtype.kind != "i":
                    continue
                nelem = jw.aval_nelem(aval)
                if nelem <= spec.scalar_allowance:
                    continue
                if gather_declared:
                    stats["int_wire_ops"] += 1
                    wire_roots.append(operand)
                else:
                    violations.append(Violation(
                        "W001", _fmt_where(eqn, axes),
                        f"undeclared integer gather: {aval.dtype} tensor of "
                        f"{nelem} elements rides a {jw.COLLECTIVES[name]} "
                        f"over dp axes {axes}, but the spec declares a "
                        f"'{spec.wire_transport}' transport with "
                        f"overlap='{spec.overlap}' — integer payload on a "
                        f"gather must come from a gather-transport codec or "
                        f"the ring route's finishing all-gather",
                    ))
            continue
        n_ax = 1
        for a in axes:
            n_ax *= spec.axis_sizes.get(a, 1)
        for operand, ival in zip(eqn.invars, ins):
            aval = getattr(operand, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            kind = aval.dtype.kind
            nelem = jw.aval_nelem(aval)
            where = _fmt_where(eqn, axes)
            if kind == "f":
                if nelem <= spec.scalar_allowance:
                    stats["scalar_float_reduces"] += 1
                else:
                    violations.append(Violation(
                        "W001", where,
                        f"float {aval.dtype} tensor of {nelem} elements on a "
                        f"{jw.COLLECTIVES[name]} over dp axes {axes} — the "
                        f"wire must carry integers (scalar allowance is "
                        f"{spec.scalar_allowance} elements)",
                    ))
            elif kind == "i":
                stats["int_wire_ops"] += 1
                wire_roots.append(operand)
                if not ival.bounded:
                    violations.append(Violation(
                        "W002", where,
                        "integer wire operand is not provably bounded — no "
                        "clip dominates this value on its way to the "
                        "collective",
                    ))
                    continue
                lane = _LANE_MAX.get(str(aval.dtype))
                if lane is not None:
                    # narrow dense lane: a psum multiplies the per-worker
                    # value by the axis product; a ring hop's operand
                    # already contains its accumulated partials
                    post = ival.scale(n_ax) if name != "ppermute" else ival
                    if post.mag > lane:
                        violations.append(Violation(
                            "W002", where,
                            f"lane overflow: |value| ≤ {int(post.mag)} after "
                            f"the {n_ax}-worker sum exceeds the "
                            f"{aval.dtype} range ±{lane}",
                        ))

    # ---- observed-clip re-proof (forgot-n_accum bug class) -------------
    if wire_roots:
        graph = jw.build_graph(closed_jaxpr)
        upstream = backward_wire_eqns(wire_roots, graph)
        # The §5.1 clip runs in the float domain just before the cast to the
        # lane dtype (round → clip → astype), so a clamp counts as a WIRE
        # clip when its output is integer OR is consumed by an int
        # convert_element_type inside the wire's backward slice. Plain float
        # clamps deeper in the model graph (logit caps etc.) stay excluded.
        int_convert_srcs: set = set()
        for key in order:
            eqn, _ins, _outs = obs[key]
            if (eqn.primitive.name == "convert_element_type"
                    and id(eqn) in upstream
                    and eqn.outvars[0].aval.dtype.kind == "i"):
                int_convert_srcs.update(
                    id(v) for v in eqn.invars if _is_var(v)
                )
        for key in order:
            eqn, ins, _outs = obs[key]
            if id(eqn) not in upstream:
                continue
            name = eqn.primitive.name
            if name == "clamp":  # lax.clamp(min, x, max)
                lo, hi = ins[0], ins[2]
            elif (name in jw.CALL_PRIMS
                    and eqn.params.get("name") == "clip"
                    and len(ins) == 3):  # jnp.clip -> pjit[name=clip](x, lo, hi)
                lo, hi = ins[1], ins[2]
            else:
                continue
            if (eqn.outvars[0].aval.dtype.kind != "i"
                    and id(eqn.outvars[0]) not in int_convert_srcs):
                continue
            if not (lo.bounded and hi.bounded):
                continue
            stats["clips_checked"] += 1
            l_obs = int(max(abs(lo.lo), abs(hi.hi)))
            if l_obs <= spec.lim:
                continue
            re_proof = iv.wire_chain_proof(
                spec.wire_kind, spec.bits, spec.n_workers, spec.n_accum,
                lim=l_obs,
            )
            for check_id, msg in re_proof.violations:
                violations.append(Violation(
                    "W002",
                    f"{_fmt_where(eqn, ())}→wire",
                    f"observed clip |v| ≤ {l_obs} is looser than the "
                    f"declared §5.1 limit {spec.lim} and breaks the chain "
                    f"proof [{check_id}]: {msg}",
                ))

    # ---- fused-route image locality ------------------------------------
    if spec.fused and spec.wire_kind == "packed":
        for key in order:
            eqn, _ins, _outs = obs[key]
            if eqn.primitive.name != "pallas_call":
                continue
            image = max(
                (jw.aval_nelem(v.aval) for v in eqn.outvars
                 if v.aval.dtype.kind == "f"),
                default=0,
            )
            if not image:
                continue
            for operand in eqn.invars:
                aval = operand.aval
                if (aval.dtype.kind == "i"
                        and jw.aval_nelem(aval) > (image * 3) // 4):
                    violations.append(Violation(
                        "W003",
                        f"pallas_call {aval.dtype}{tuple(aval.shape)}",
                        f"int32 kernel operand of {jw.aval_nelem(aval)} "
                        f"elements is image-sized (image {image}): the "
                        f"unpacked integer image took an HBM round-trip "
                        f"instead of riding the packed words "
                        f"(expected ≤ {image // (32 // spec.bits)} words)",
                    ))

    kept: List[Violation] = []
    suppressed: List[Tuple[Violation, str]] = []
    for v in violations:
        if v.rule in suppress:
            suppressed.append((v, suppress[v.rule]))
        else:
            kept.append(v)
    return AuditReport(
        spec=spec,
        proof=proof,
        violations=tuple(kept),
        suppressed=tuple(suppressed),
        stats=stats,
    )


def audit_step(artifacts, which: str = "compressed", **kw) -> AuditReport:
    """Trace one jitted variant of a built step and audit it against the
    spec the builder attached (``StepArtifacts.audit_spec``)."""
    import jax  # deferred: the lint half of repro.analysis is jax-free

    spec = getattr(artifacts, "audit_spec", None)
    if spec is None:
        raise ValueError(
            "StepArtifacts carries no audit_spec — build the step with "
            "repro.launch.step.build_train_step (PR 8+) or pass audit_jaxpr "
            "an explicit WireSpec"
        )
    jaxpr = jax.make_jaxpr(artifacts.jitted[which])(*artifacts.arg_structs)
    return audit_jaxpr(jaxpr, spec, **kw)
