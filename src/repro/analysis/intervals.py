"""Interval abstract domain + the floatless-wire range proofs.

Two layers, both pure Python over exact integer arithmetic (no jax import
needed to USE the domain; the jaxpr evaluator takes already-traced jaxprs):

1. :class:`Interval` and :func:`eval_jaxpr_intervals` — a forward abstract
   interpretation of a jaxpr in the interval domain. Transfer functions
   cover the integer wire chain exactly (clamp, add, shifts, masks, the
   collectives); everything else soundly widens to TOP. Scans are unrolled
   up to ``scan_cap`` iterations (the microbatch accumulator has static
   length M), beyond that carries widen. This is what turns "the encode
   clip makes the ring safe" from a build-time point check into a property
   of the traced program: the n-hop partial-sum growth is *derived* by the
   evaluator from the unrolled ppermute chain, not assumed.

2. :func:`wire_chain_proof` — the codec-level §5.1 proof for a declared
   (kind, bits, n_workers, n_accum): symbolic stage intervals for
   encode → M-accumulate → pack → n-worker wire sum → unpack, checked
   against the guard-bit invariant. The ``WireRangeError`` condition
   (degenerate clip limit) is one of its violations rather than a runtime
   raise. ``lim`` may be overridden with a clip bound *observed in the
   jaxpr* so a clip that is looser than the declared limit (the
   forgot-``n_accum`` bug class) fails the same proof.

tests/test_analysis.py's hypothesis suite checks soundness: concrete
random chains always land inside the derived stage intervals.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

from repro.analysis.jaxpr_walk import COLLECTIVES, eqn_axes

__all__ = [
    "Interval",
    "TOP",
    "eval_jaxpr_intervals",
    "wire_chain_proof",
    "ChainProof",
    "int_range_max",
    "safe_clip_limit",
    "declared_clip_limit",
]

_INF = math.inf

# value range of a signed `bits`-wide field (mirrors wire.base._INT_RANGE,
# duplicated so this module stays importable without jax)
_INT_RANGE = {4: 7, 8: 127, 16: 32767, 32: 2147483647}


def int_range_max(bits: int) -> int:
    return _INT_RANGE[bits]


def safe_clip_limit(n_contrib: int, bits: int) -> int:
    """§5.1 limit ``(2^(b-1)-1)//n`` WITHOUT the WireRangeError raise —
    the proof reports lim==0 as a violation instead of throwing."""
    return _INT_RANGE[bits] // max(int(n_contrib), 1)


def declared_clip_limit(kind: str, n_contrib: int, bits: int) -> int:
    """The clip limit a (kind, bits) codec declares for ``n_contrib``
    summed contributions. Psum-transport kinds divide the value range by n
    (§5.1: the sum happens ON the wire); the gather-transport "topk" kind
    clips at the full range — nothing sums until the decode-side
    scatter-add, whose int32 bound is the chain proof's job."""
    if kind == "topk":
        return _INT_RANGE[bits]
    return safe_clip_limit(n_contrib, bits)


# --------------------------------------------------------------------------
# the domain
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Interval:
    """Closed interval [lo, hi] over the extended reals; exact (Python int)
    endpoints wherever the program is exact."""

    lo: float
    hi: float

    def __post_init__(self):
        assert self.lo <= self.hi, (self.lo, self.hi)

    # -- queries ---------------------------------------------------------
    @property
    def bounded(self) -> bool:
        return self.lo != -_INF and self.hi != _INF

    @property
    def mag(self) -> float:
        """max |v| over the interval."""
        return max(abs(self.lo), abs(self.hi))

    def contains(self, v) -> bool:
        return self.lo <= v <= self.hi

    # -- lattice ---------------------------------------------------------
    def union(self, o: "Interval") -> "Interval":
        return Interval(min(self.lo, o.lo), max(self.hi, o.hi))

    # -- arithmetic ------------------------------------------------------
    def add(self, o: "Interval") -> "Interval":
        return Interval(self.lo + o.lo, self.hi + o.hi)

    def sub(self, o: "Interval") -> "Interval":
        return Interval(self.lo - o.hi, self.hi - o.lo)

    def neg(self) -> "Interval":
        return Interval(-self.hi, -self.lo)

    def mul(self, o: "Interval") -> "Interval":
        if not (self.bounded and o.bounded):
            return TOP
        ps = [self.lo * o.lo, self.lo * o.hi, self.hi * o.lo, self.hi * o.hi]
        return Interval(min(ps), max(ps))

    def scale(self, c) -> "Interval":
        """Multiply by the exact scalar c (axis size, reduced-element count)."""
        if not self.bounded:
            return TOP
        a, b = self.lo * c, self.hi * c
        return Interval(min(a, b), max(a, b))

    def shl(self, s: "Interval") -> "Interval":
        if not (self.bounded and s.bounded) or s.lo < 0:
            return TOP
        ps = [
            int(self.lo) << int(s.lo), int(self.lo) << int(s.hi),
            int(self.hi) << int(s.lo), int(self.hi) << int(s.hi),
        ]
        return Interval(min(ps), max(ps))

    def clamp(self, lo: "Interval", hi: "Interval") -> "Interval":
        """lax.clamp(lo, x, hi): result ⊆ [lo.lo, hi.hi] REGARDLESS of x —
        this is the transfer that bounds the encode clip for TOP operands."""
        return Interval(
            max(self.lo, lo.lo) if self.bounded else lo.lo,
            min(self.hi, hi.hi) if self.bounded else hi.hi,
        )

    @staticmethod
    def point(v) -> "Interval":
        return Interval(v, v)

    @staticmethod
    def from_value(v) -> "Interval":
        """Interval of a concrete scalar/array constant."""
        import numpy as np

        a = np.asarray(v)
        if a.size == 0:
            return Interval.point(0)
        if a.dtype == bool:
            return Interval(0, 1)
        if not np.issubdtype(a.dtype, np.number):
            return TOP
        lo, hi = a.min(), a.max()
        if np.issubdtype(a.dtype, np.integer):
            return Interval(int(lo), int(hi))
        if not (np.isfinite(lo) and np.isfinite(hi)):
            return TOP
        return Interval(float(lo), float(hi))


TOP = Interval(-_INF, _INF)
_MASKABLE = Interval(0, _INF)


# --------------------------------------------------------------------------
# forward jaxpr evaluation
# --------------------------------------------------------------------------
def _passthrough(ins, eqn):
    return ins[0]


def _nelem(aval) -> int:
    n = 1
    for s in getattr(aval, "shape", ()):
        n *= int(s)
    return n


def _reduce_count(eqn) -> int:
    """#elements folded into each output element of a reduce_* eqn."""
    out = _nelem(eqn.outvars[0].aval)
    inn = _nelem(eqn.invars[0].aval)
    return max(inn // max(out, 1), 1)


def _and_transfer(ins, eqn):
    # x & mask with a known non-negative mask bounds the result to [0, mask]
    for m in ins:
        if m.bounded and m.lo >= 0:
            return Interval(0, m.hi)
    return TOP


def _scatter_add_transfer(ins, eqn):
    # out = operand with U update elements added at (possibly colliding)
    # indices: each output element receives between 0 and U updates, so
    # out ⊆ operand + hull(0, U·updates). Coarse but sound — and exactly
    # what bounds the gather wire's decode image (n·k top-k values
    # scatter-added into zeros).
    if len(ins) < 3 or not (ins[0].bounded and ins[2].bounded):
        return TOP
    U = _nelem(eqn.invars[2].aval)
    lo = min(0, ins[2].lo * U)
    hi = max(0, ins[2].hi * U)
    return ins[0].add(Interval(lo, hi))


def _top_k_transfer(ins, eqn):
    # (values, indices): values are a subset of the input, indices address
    # the input's trailing dim
    shape = getattr(eqn.invars[0].aval, "shape", ())
    d = int(shape[-1]) if shape else 1
    return [ins[0], Interval(0, max(d - 1, 0))]


_TRANSFER: Dict[str, Callable] = {
    "add": lambda ins, e: ins[0].add(ins[1]),
    "sub": lambda ins, e: ins[0].sub(ins[1]),
    "mul": lambda ins, e: ins[0].mul(ins[1]),
    "neg": lambda ins, e: ins[0].neg(),
    "max": lambda ins, e: Interval(max(ins[0].lo, ins[1].lo), max(ins[0].hi, ins[1].hi)),
    "min": lambda ins, e: Interval(min(ins[0].lo, ins[1].lo), min(ins[0].hi, ins[1].hi)),
    "clamp": lambda ins, e: ins[1].clamp(ins[0], ins[2]),
    "shift_left": lambda ins, e: ins[0].shl(ins[1]),
    "and": _and_transfer,
    "abs": lambda ins, e: Interval(0, ins[0].mag) if ins[0].bounded else _MASKABLE,
    "sign": lambda ins, e: Interval(-1, 1),
    "floor": _passthrough,
    "ceil": lambda ins, e: Interval(ins[0].lo, ins[0].hi + 1) if ins[0].bounded else TOP,
    "round": lambda ins, e: Interval(ins[0].lo - 1, ins[0].hi + 1) if ins[0].bounded else TOP,
    "convert_element_type": _passthrough,
    "reshape": _passthrough,
    "broadcast_in_dim": _passthrough,
    "transpose": _passthrough,
    "squeeze": _passthrough,
    "rev": _passthrough,
    "slice": _passthrough,
    "dynamic_slice": lambda ins, e: ins[0],
    "gather": lambda ins, e: ins[0],
    "expand_dims": _passthrough,
    "copy": _passthrough,
    "reshard": _passthrough,
    "pvary": _passthrough,
    "stop_gradient": _passthrough,
    "optimization_barrier": None,  # multi-out passthrough, handled below
    "concatenate": lambda ins, e: _union_all(ins),
    "pad": lambda ins, e: ins[0].union(ins[1]),
    "dynamic_update_slice": lambda ins, e: ins[0].union(ins[1]),
    "select_n": lambda ins, e: _union_all(ins[1:]),
    "reduce_sum": lambda ins, e: ins[0].scale(_reduce_count(e)),
    "reduce_max": _passthrough,
    "reduce_min": _passthrough,
    "reduce_and": lambda ins, e: Interval(0, 1),
    "reduce_or": lambda ins, e: Interval(0, 1),
    "iota": lambda ins, e: Interval(0, max(_nelem(e.outvars[0].aval) - 1, 0)),
    "rem": lambda ins, e: Interval(-ins[1].mag, ins[1].mag) if ins[1].bounded else TOP,
    "scatter-add": _scatter_add_transfer,
    "scatter": lambda ins, e: ins[0].union(ins[2]) if len(ins) >= 3 else TOP,
}

_CMP = ("eq", "ne", "lt", "le", "gt", "ge", "is_finite")


def _union_all(ivals: List[Interval]) -> Interval:
    out = ivals[0]
    for i in ivals[1:]:
        out = out.union(i)
    return out


def _closed(j):
    """(jaxpr, consts) from ClosedJaxpr | Jaxpr."""
    if hasattr(j, "jaxpr"):
        return j.jaxpr, list(j.consts)
    return j, []


class _Eval:
    def __init__(self, axis_sizes, prim_overrides, on_eqn, scan_cap):
        self.axis_sizes = dict(axis_sizes or {})
        self.overrides = dict(prim_overrides or {})
        self.on_eqn = on_eqn
        self.scan_cap = scan_cap

    # -- env helpers -----------------------------------------------------
    def read(self, env, atom) -> Interval:
        if hasattr(atom, "val"):  # Literal
            return Interval.from_value(atom.val)
        return env.get(id(atom), TOP)

    def bind(self, env, jaxpr, consts, in_ivals):
        for v, c in zip(jaxpr.constvars, consts):
            env[id(v)] = Interval.from_value(c)
        for v, i in zip(jaxpr.invars, in_ivals):
            env[id(v)] = i

    # -- collectives -----------------------------------------------------
    def _axis_prod(self, eqn) -> Optional[int]:
        n = 1
        for a in eqn_axes(eqn):
            if a not in self.axis_sizes:
                return None
            n *= self.axis_sizes[a]
        return n

    def _collective(self, eqn, ins) -> List[Interval]:
        name = eqn.primitive.name
        if name in ("psum", "psum_invariant", "psum_scatter", "reduce_scatter"):
            n = self._axis_prod(eqn)
            if n is None:
                return [TOP for _ in eqn.outvars]
            return [i.scale(n) for i in ins]
        if name == "pmean":
            return list(ins)
        # pmax/pmin/all_gather/ppermute/all_to_all: element values unchanged
        return list(ins[: len(eqn.outvars)]) or [TOP for _ in eqn.outvars]

    # -- structured control flow -----------------------------------------
    def _eval_scan(self, eqn, ins) -> List[Interval]:
        body, consts = _closed(eqn.params["jaxpr"])
        nc = eqn.params["num_consts"]
        nk = eqn.params["num_carry"]
        length = int(eqn.params.get("length", self.scan_cap + 1))
        cs, carry, xs = ins[:nc], ins[nc: nc + nk], ins[nc + nk:]
        n_ys = len(body.outvars) - nk
        ys = [None] * n_ys
        if length <= self.scan_cap:
            # exact unrolled evaluation — this is what derives the M-microbatch
            # integer accumulator bound [−M·lim, M·lim] instead of assuming it
            for _ in range(length):
                outs = self.eval(body, consts, cs + carry + xs)
                carry = outs[:nk]
                ys = [y if y2 is None else (y2 if y is None else y.union(y2))
                      for y, y2 in zip(outs[nk:], ys)]
            return carry + [y if y is not None else TOP for y in ys]
        # widen: iterate to fixpoint a few rounds, then TOP the unstable carries
        for _ in range(4):
            outs = self.eval(body, consts, cs + carry + xs)
            new_carry = [a.union(b) for a, b in zip(carry, outs[:nk])]
            if new_carry == carry:
                return carry + outs[nk:]
            carry = new_carry
        carry = [c if c == o else TOP
                 for c, o in zip(carry, self.eval(body, consts, cs + carry + xs)[:nk])]
        outs = self.eval(body, consts, cs + carry + xs)
        return carry + outs[nk:]

    def _eval_while(self, eqn, ins) -> List[Interval]:
        body, bconsts = _closed(eqn.params["body_jaxpr"])
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        bcs = ins[cn: cn + bn]
        carry = ins[cn + bn:]
        for _ in range(4):
            outs = self.eval(body, bconsts, bcs + carry)
            new_carry = [a.union(b) for a, b in zip(carry, outs)]
            if new_carry == carry:
                return carry
            carry = new_carry
        return [TOP] * len(carry)

    def _eval_cond(self, eqn, ins) -> List[Interval]:
        outs = None
        for br in eqn.params["branches"]:
            sub, consts = _closed(br)
            o = self.eval(sub, consts, ins[1:])
            outs = o if outs is None else [a.union(b) for a, b in zip(outs, o)]
        return outs if outs is not None else [TOP] * len(eqn.outvars)

    # -- generic call-style recursion ------------------------------------
    def _eval_call(self, eqn, ins) -> Optional[List[Interval]]:
        for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if k in eqn.params:
                sub, consts = _closed(eqn.params[k])
                if len(sub.invars) == len(ins) and len(sub.outvars) == len(eqn.outvars):
                    return self.eval(sub, consts, ins)
        return None

    # -- the interpreter loop --------------------------------------------
    def eval(self, jaxpr, consts, in_ivals) -> List[Interval]:
        env: Dict[int, Interval] = {}
        self.bind(env, jaxpr, consts, in_ivals)
        for eqn in jaxpr.eqns:
            ins = [self.read(env, a) for a in eqn.invars]
            name = eqn.primitive.name
            outs: Optional[List[Interval]] = None
            if name in self.overrides:
                outs = self.overrides[name](eqn, ins)
            if outs is None:
                if name in COLLECTIVES:
                    outs = self._collective(eqn, ins)
                elif name == "scan":
                    outs = self._eval_scan(eqn, ins)
                elif name == "while":
                    outs = self._eval_while(eqn, ins)
                elif name == "cond":
                    outs = self._eval_cond(eqn, ins)
                elif name == "optimization_barrier":
                    outs = list(ins)
                elif name == "top_k":
                    outs = _top_k_transfer(ins, eqn)
                elif name in _CMP:
                    outs = [Interval(0, 1) for _ in eqn.outvars]
                elif name in _TRANSFER:
                    outs = [_TRANSFER[name](ins, eqn)]
                else:
                    outs = self._eval_call(eqn, ins)
                    if outs is None:
                        outs = [TOP for _ in eqn.outvars]
            if len(outs) != len(eqn.outvars):
                outs = [TOP for _ in eqn.outvars]
            for v, o in zip(eqn.outvars, outs):
                env[id(v)] = o
            if self.on_eqn is not None:
                self.on_eqn(eqn, ins, outs)
        return [self.read(env, v) for v in jaxpr.outvars]


def eval_jaxpr_intervals(
    closed_jaxpr,
    in_ivals: Optional[List[Interval]] = None,
    *,
    axis_sizes: Optional[Dict[str, int]] = None,
    prim_overrides: Optional[Dict[str, Callable]] = None,
    on_eqn: Optional[Callable] = None,
    scan_cap: int = 8,
) -> List[Interval]:
    """Forward interval evaluation of a (Closed)Jaxpr.

    ``axis_sizes`` maps mesh axis names to sizes so psum-style collectives
    can scale soundly (unknown axes widen to TOP). ``prim_overrides`` maps a
    primitive name to ``fn(eqn, in_ivals) -> [out_ivals] | None`` — the wire
    auditor uses it to install the trusted encode-kernel contract for
    ``pallas_call``. ``on_eqn(eqn, in_ivals, out_ivals)`` observes every
    evaluated eqn (an eqn inside a scan body is observed once per unrolled
    iteration — observers union by eqn identity).
    """
    jaxpr, consts = _closed(closed_jaxpr)
    if in_ivals is None:
        in_ivals = [TOP] * len(jaxpr.invars)
    ev = _Eval(axis_sizes, prim_overrides, on_eqn, scan_cap)
    return ev.eval(jaxpr, consts, list(in_ivals))


# --------------------------------------------------------------------------
# codec-level chain proof
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ChainProof:
    """Symbolic §5.1 proof for one declared wire configuration.

    Stage intervals are exact bounds on any run respecting the declared
    clip: `encode` one microbatch's image, `accum` the M-microbatch local
    accumulator, `packed_field` one worker's biased transport field
    (packed) or lane value (dense), `wire_partial` any j≤n partial sum a
    ring hop may carry, `wire_sum` the full n-worker field/lane sum, and
    `image_sum` the unpacked integer image. `violations` is non-empty iff
    the configuration can overflow/degenerate; each entry is
    ``(check_id, human message)``.
    """

    kind: str
    bits: int
    n_workers: int
    n_accum: int
    lim: int
    stages: Dict[str, Interval]
    violations: Tuple[Tuple[str, str], ...]

    @property
    def ok(self) -> bool:
        return not self.violations


def wire_chain_proof(
    kind: str,
    bits: int,
    n_workers: int,
    n_accum: int = 1,
    lim: Optional[int] = None,
) -> ChainProof:
    """Prove (or refute) the guard-bit invariant for one wire config.

    ``lim`` defaults to the declared §5.1 limit ``clip_limit(n·M)``; pass a
    clip bound observed in a traced jaxpr to check a *looser-than-declared*
    clip against the same overflow conditions (the forgot-``n_accum`` bug
    class fails here even though the declared config is fine).
    """
    if kind not in ("dense", "packed", "topk"):
        raise ValueError(f"unknown wire kind {kind!r}")
    n, M = int(n_workers), int(n_accum)
    R = int_range_max(bits)
    lim_declared = declared_clip_limit(kind, n * M, bits)
    L = lim_declared if lim is None else int(lim)
    bad: List[Tuple[str, str]] = []
    if L <= 0:
        bad.append((
            "degenerate-clip",
            f"clip limit (2^{bits - 1}-1)//{n * M} == 0 for {n} workers × "
            f"{M} microbatches on an int{bits} wire: every gradient entry "
            f"would be clipped to 0 (the WireRangeError condition)",
        ))
        L = 0

    encode = Interval(-L, L)
    accum = encode.scale(M)
    stages: Dict[str, Interval] = {"encode": encode, "accum": accum}

    if kind == "dense":
        # lane value is the accumulator itself; ring partials / the psum grow
        # it by up to n contributions, all of which must fit the lane range
        field = accum
        wire_sum = accum.scale(n)
        stages["packed_field"] = field
        stages["wire_partial"] = wire_sum  # j≤n partials ⊆ the n-worker hull
        stages["wire_sum"] = wire_sum
        lane_max = R if bits < 32 else _INT_RANGE[32]
        if wire_sum.mag > lane_max:
            bad.append((
                "lane-overflow",
                f"n-worker lane sum |Σ| ≤ {int(wire_sum.mag)} exceeds the "
                f"int{bits} lane range ±{lane_max} (clip |v| ≤ {L} is too "
                f"loose for {n} workers × {M} microbatches)",
            ))
    elif kind == "topk":
        # gather transport: every field crosses the wire UNSUMMED as a plain
        # two's-complement `bits`-wide value next to its int32 index — no
        # bias, no field-to-field addition, and no pipelined pre-pack
        # accumulation either (topk is never fused: each of the M images is
        # encoded fresh at ±L), so the field is the ENCODE stage and the
        # only field condition is that the (possibly observed) clip fits
        # the value width. Partial and full "wire sums" are the field
        # itself: the sum happens after transport, in the scatter-add
        # image checked below — which is where the n·M product bites.
        field = encode
        stages["packed_field"] = field
        stages["wire_partial"] = field
        stages["wire_sum"] = field
        if field.mag > R:
            bad.append((
                "field-overflow",
                f"topk value field |v| ≤ {int(field.mag)} exceeds the "
                f"int{bits} two's-complement range ±{R} (clip |v| ≤ {L} "
                f"is wider than the value plane)",
            ))
    else:
        # packed: pack() biases every field by clip_limit(n) (the bias the
        # unpack side subtracts n× of), while values are bounded by the
        # pipelined clip M·clip_limit(n·M) ≤ clip_limit(n)
        bias = safe_clip_limit(n, bits)
        field = accum.add(Interval.point(bias))
        wire_sum = field.scale(n)
        stages["packed_field"] = field
        stages["wire_partial"] = Interval(
            min(0, wire_sum.lo), max(0, wire_sum.hi)
        )  # a j-hop partial is j ≤ n biased fields; hull includes j=0
        stages["wire_sum"] = wire_sum
        if field.lo < 0:
            bad.append((
                "field-underflow",
                f"biased field v+{bias} can reach {int(field.lo)} < 0 "
                f"(clip |v| ≤ {L} with {M} microbatches exceeds the "
                f"pack bias clip_limit({n}) = {bias}): a negative field "
                f"borrows from its packed neighbour",
            ))
        if wire_sum.hi > (1 << bits) - 2:
            bad.append((
                "field-overflow",
                f"{n}-worker biased field sum can reach "
                f"{int(wire_sum.hi)} > 2^{bits}-2 = {(1 << bits) - 2}: the "
                f"field carries into its packed neighbour (clip |v| ≤ {L} "
                f"is too loose for {n} workers × {M} microbatches)",
            ))

    image = accum.scale(n)
    stages["image_sum"] = image
    if image.mag > _INT_RANGE[32]:
        bad.append((
            "image-overflow",
            f"summed integer image |Σ| ≤ {int(image.mag)} exceeds int32",
        ))
    return ChainProof(
        kind=kind, bits=bits, n_workers=n, n_accum=M,
        lim=L, stages=stages, violations=tuple(bad),
    )
