"""Static wire-traffic accountant: bytes and collective counts, proven.

PR 3/PR 4 pinned the wire's cheapness claims with runtime ``--check`` smokes
(count collectives on one debug mesh, meter bytes with the ``Logged`` codec).
This module makes the same numbers a THEOREM about the traced step: from the
:class:`~repro.analysis.wire_audit.WireSpec`'s transport declaration alone
(per-leaf image sizes, codec, overlap mode, bucket size) it reconstructs the
exact eqn-level transport the step must emit —

  * ``overlap="off"``:  one psum of the whole words tree per microbatch
    image, carrying exactly the codec payload
    (``Σ wire_bytes(leaf)`` — what ``Logged.pack_bytes`` meters per image
    and what ``BucketManifest.payload_bytes`` records);
  * ``overlap="ring"``: per image and bucket of size s, for every dp axis of
    size n > 1: (n-1) ppermute hops + 1 all_gather, each carrying a
    ⌈s/n⌉-word chunk (``ring_allreduce_int`` pads s to n·⌈s/n⌉; the padding
    is reported, not hidden) — a size-1 axis short-circuits in Python and
    emits nothing;
  * gather transport (``wire_transport="gather"``, TopKInt): per image the
    bucketized payload (one bucket serial; ``bucket_words`` cuts under ring
    overlap) rides one all_gather per dp axis of size > 1, operands
    COMPOUNDING across axes (the second axis gathers the first's stacked
    output) — exactly ``BucketManifest.gather_collectives``.

— then walks the jaxpr and demands the observed wire collectives match:

  T001  observed wire-collective BYTES ≠ the declared transport model's
        (payload drift: a codec re-encoding, an accidental widening, a
        bucketing change that inflates the wire);
  T002  observed wire-collective COUNT ≠ the declared transport model's
        (transport-shape drift: a fused/elided/duplicated collective — the
        static twin of bench_overlap's "12 bucketed vs 1 serial" gate).

The declared payload is BY CONSTRUCTION the number the runtime meters agree
on (``Logged`` calls the same ``wire_bytes`` arithmetic per pack;
``plan_buckets`` cuts the same word total), which tests/test_schedule.py
pins across every codec × n × M; T001/T002 then extend that equality to the
traced eqns, making BENCH_comm_volume/BENCH_overlap cross-checkable without
executing anything.

Wire-collective identification (shared with :mod:`repro.analysis.schedule`
and benchmarks/bench_overlap.py's runtime counter): a collective eqn over
any declared dp axis with an integer operand, of a kind that can carry the
transport ({psum, ppermute, all_gather, reduce_scatter, psum_scatter}).
Float collectives (loss/metric reductions, ZeRO-1 bf16 gathers) and
model-axis traffic are out of scope here — wire_audit's W001 owns them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.analysis import jaxpr_walk as jw
from repro.analysis.wire_audit import Violation, WireSpec

__all__ = [
    "RULES",
    "WIRE_COLLECTIVE_PRIMS",
    "TransportPlan",
    "TrafficReport",
    "leaf_wire_words",
    "word_itemsize",
    "payload_bytes",
    "plan_bucket_sizes",
    "plan_transport",
    "wire_collective_eqns",
    "account_traffic",
]

RULES = {
    "T001": "observed wire-collective bytes equal the declared transport "
            "model's (codec payload + ring chunk padding)",
    "T002": "observed wire-collective count equals the declared transport "
            "model's (serial: 1 psum/image; ring: n_ax collectives per "
            "bucket per dp axis)",
}

# collective kinds that can carry transport words; gathers included because
# the ring's finished chunks ride an all_gather. pmax/pmin/all_to_all never
# carry the wire (metrics / MoE shuffles) and are excluded so they can't
# pollute the byte account.
WIRE_COLLECTIVE_PRIMS = frozenset(
    {"psum", "psum_invariant", "ppermute", "all_gather", "reduce_scatter",
     "psum_scatter"}
)


# ---------------------------------------------------------------------------
# declared-side arithmetic (jax-free: mirrors repro.wire without importing it)
# ---------------------------------------------------------------------------
def word_itemsize(kind: str, bits: int) -> int:
    """Transport word size in bytes: PackedInt and TopKInt always ride
    int32 words (topk: int32 index plane + bit-packed value words);
    DenseInt rides the narrowest native lane holding one value (mirrors
    repro.wire.dense._LANE)."""
    if kind in ("packed", "topk"):
        return 4
    return 1 if bits <= 8 else (2 if bits <= 16 else 4)


def leaf_wire_words(kind: str, bits: int, size: int, *, k: int = 0) -> int:
    """Transport words one leaf of ``size`` elements packs into (mirrors
    PackedInt.words_len / DenseInt's identity layout / TopKInt's
    idx-plane + bit-packed vals-plane split, all int32 words)."""
    if kind == "packed":
        f = 32 // bits
        return -(-int(size) // f)
    if kind == "topk":
        k_eff = min(int(k), int(size)) if k else int(size)
        f = 32 // bits
        return k_eff + -(-k_eff // f)
    return int(size)


def payload_bytes(kind: str, bits: int, size: int, *, k: int = 0) -> int:
    """Exact wire bytes for one leaf — equals ``WireFormat.wire_bytes(size)``
    and therefore what ``Logged`` meters per pack call."""
    return leaf_wire_words(kind, bits, size, k=k) * word_itemsize(kind, bits)


def plan_bucket_sizes(total_words: int, bucket_words: int) -> Tuple[int, ...]:
    """Bucket word counts for a ``total_words`` payload — the same cut as
    ``repro.wire.bucketing.plan_buckets`` (full buckets + ragged tail),
    kept jax-free here and pinned equal by tests/test_schedule.py."""
    if bucket_words <= 0:
        raise ValueError(f"bucket_words must be positive, got {bucket_words}")
    full, tail = divmod(int(total_words), int(bucket_words))
    return (bucket_words,) * full + ((tail,) if tail else ())


@dataclasses.dataclass(frozen=True)
class TransportPlan:
    """The eqn-level transport a spec declares, per STEP (all microbatch
    images)."""

    payload_bytes: int      # codec payload, one image (== Logged per image)
    total_words: int        # transport words, one image
    n_buckets: int          # 0 on the serial route
    n_eqns: int             # wire collectives the whole step must emit
    coll_bytes: int         # total operand bytes those eqns carry
    padding_bytes: int      # ring chunk padding included in coll_bytes
    by_prim: Dict[str, int]  # prim name -> eqn count (whole step)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["by_prim"] = dict(d["by_prim"])
        return d


def plan_transport(spec: WireSpec) -> Optional[TransportPlan]:
    """Reconstruct the declared transport from the spec alone — or None when
    the spec carries no leaf sizes (hand-built specs can't be accounted)."""
    if not spec.leaf_sizes:
        return None
    kind, bits = spec.wire_kind, spec.bits
    itemsize = word_itemsize(kind, bits)
    words = [
        leaf_wire_words(kind, bits, s, k=spec.topk_k) for s in spec.leaf_sizes
    ]
    total_words = sum(words)
    payload = total_words * itemsize
    by_prim: Dict[str, int] = {}
    if spec.wire_transport == "gather":
        # Gather route (CommCtx._gather_wire): the payload is always
        # bucketized — at bucket_words under ring overlap, as ONE bucket
        # otherwise — and each bucket rides one all_gather per dp axis of
        # size > 1 (size-1 axes short-circuit in allgather_wire_words and
        # emit nothing). The gathers COMPOUND: the second axis gathers the
        # first axis's already-stacked output, so its operand is n₁× the
        # bucket — the same arithmetic as BucketManifest.gather_collectives,
        # pinned equal by tests. No chunk padding: plan_buckets cuts a
        # ragged tail and the gather ships it as-is.
        if spec.overlap == "ring" and spec.bucket_words:
            buckets = plan_bucket_sizes(total_words, spec.bucket_words)
        else:
            buckets = (total_words,) if total_words else ()
        gather_axes = [n for n in spec.dp_sizes if n > 1]
        coll_words = 0
        eqns = 0
        for s in buckets:
            grown = s
            for n in reversed(gather_axes):
                coll_words += grown
                eqns += 1
                by_prim["all_gather"] = by_prim.get("all_gather", 0) + 1
                grown *= n
        return TransportPlan(
            payload_bytes=payload,
            total_words=total_words,
            n_buckets=len(buckets),
            n_eqns=eqns * spec.n_accum,
            coll_bytes=coll_words * itemsize * spec.n_accum,
            padding_bytes=0,
            by_prim={p: v * spec.n_accum for p, v in by_prim.items()},
        )
    if spec.overlap == "ring":
        buckets = plan_bucket_sizes(
            total_words, spec.bucket_words or total_words
        )
        ring_axes = [n for n in spec.dp_sizes if n > 1]
        coll_words = 0
        eqns = 0
        for s in buckets:
            for n in ring_axes:
                chunk = -(-s // n)
                coll_words += n * chunk  # (n-1) ppermute hops + 1 gather
                eqns += n
                by_prim["ppermute"] = by_prim.get("ppermute", 0) + (n - 1)
                by_prim["all_gather"] = by_prim.get("all_gather", 0) + 1
        padding = coll_words * itemsize - payload * len(ring_axes)
        plan = TransportPlan(
            payload_bytes=payload,
            total_words=total_words,
            n_buckets=len(buckets),
            n_eqns=eqns * spec.n_accum,
            coll_bytes=coll_words * itemsize * spec.n_accum,
            padding_bytes=padding * spec.n_accum,
            by_prim={k: v * spec.n_accum for k, v in by_prim.items()},
        )
    else:
        plan = TransportPlan(
            payload_bytes=payload,
            total_words=total_words,
            n_buckets=0,
            n_eqns=spec.n_accum,
            coll_bytes=payload * spec.n_accum,
            padding_bytes=0,
            by_prim={"psum": spec.n_accum},
        )
    return plan


# ---------------------------------------------------------------------------
# observed side: walk the jaxpr
# ---------------------------------------------------------------------------
def _int_operand_bytes(eqn) -> int:
    return sum(
        jw.aval_size_bytes(v.aval)
        for v in eqn.invars
        if hasattr(v, "aval")
        and getattr(v.aval, "dtype", None) is not None
        and v.aval.dtype.kind in ("i", "u")
    )


def wire_collective_eqns(jaxpr, dp_axes) -> List[Tuple[object, int]]:
    """``(eqn, multiplicity)`` for every wire collective in the tree: a
    WIRE_COLLECTIVE_PRIMS eqn over any dp axis with an integer operand."""
    dp = set(dp_axes)
    out = []
    for eqn, scale in jw.iter_eqns_scaled(jaxpr):
        if eqn.primitive.name not in WIRE_COLLECTIVE_PRIMS:
            continue
        if not (set(jw.eqn_axes(eqn)) & dp):
            continue
        if _int_operand_bytes(eqn) == 0:
            continue
        out.append((eqn, scale))
    return out


@dataclasses.dataclass
class TrafficReport:
    """Declared-vs-observed wire traffic for one traced step."""

    plan: Optional[TransportPlan]
    observed_eqns: int
    observed_bytes: int
    observed_by_prim: Dict[str, int]     # prim -> eqn count
    observed_bytes_by_prim: Dict[str, int]
    violations: Tuple[Violation, ...]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        return {
            "declared": self.plan.to_dict() if self.plan else None,
            "observed_eqns": self.observed_eqns,
            "observed_bytes": self.observed_bytes,
            "observed_by_prim": dict(self.observed_by_prim),
            "observed_bytes_by_prim": dict(self.observed_bytes_by_prim),
            "violations": [v.to_dict() for v in self.violations],
            "ok": self.ok,
        }


def account_traffic(closed_jaxpr, spec: WireSpec) -> TrafficReport:
    """Tally the traced step's wire collectives and prove them equal to the
    spec's declared transport (T001 bytes, T002 counts)."""
    top = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    by_prim: Dict[str, int] = {}
    bytes_by_prim: Dict[str, int] = {}
    n_eqns = 0
    n_bytes = 0
    for eqn, scale in wire_collective_eqns(top, spec.dp_axes):
        name = eqn.primitive.name
        b = _int_operand_bytes(eqn) * scale
        by_prim[name] = by_prim.get(name, 0) + scale
        bytes_by_prim[name] = bytes_by_prim.get(name, 0) + b
        n_eqns += scale
        n_bytes += b

    violations: List[Violation] = []
    plan = plan_transport(spec)
    if plan is not None:
        if n_bytes != plan.coll_bytes:
            violations.append(Violation(
                "T001",
                f"wire@{','.join(spec.dp_axes)}",
                f"observed wire-collective bytes {n_bytes} != declared "
                f"transport {plan.coll_bytes} (codec payload "
                f"{plan.payload_bytes} B/image × M={spec.n_accum}"
                + (f" + ring padding {plan.padding_bytes} B"
                   if plan.padding_bytes else "")
                + f"; per-prim observed {bytes_by_prim})",
            ))
        if n_eqns != plan.n_eqns:
            violations.append(Violation(
                "T002",
                f"wire@{','.join(spec.dp_axes)}",
                f"observed {n_eqns} wire collective(s) {by_prim} != declared "
                f"{plan.n_eqns} {plan.by_prim} "
                f"({spec.overlap} route, {plan.n_buckets or 'no'} bucket(s), "
                f"M={spec.n_accum})",
            ))
    return TrafficReport(
        plan=plan,
        observed_eqns=n_eqns,
        observed_bytes=n_bytes,
        observed_by_prim=by_prim,
        observed_bytes_by_prim=bytes_by_prim,
        violations=tuple(violations),
    )
