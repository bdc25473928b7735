"""Reduction of a profiler trace to what the per-layer metrics read.

A traced run writes one ``.xplane.pb``. ``load`` reads it with
``jax.profiler.ProfileData`` into plain lists: per chip, the operations of
its ``XLA Ops`` line (name, start, end in seconds on the trace's clock);
and the host spans the harness annotates (``data``, ``step``,
``loss_read``). The window is from the first traced loss read to the last. Everything after that is arithmetic on intervals, which the
tests check on recorded events:

  busy(ops, a, b)        the union of the operations' intervals in [a, b]
  gaps(ops, a, b)        the idle intervals in [a, b]
  label(t, spans)        the host span running at time t
"""
from __future__ import annotations

import dataclasses
import glob
import os

SPANS = ("data", "step", "loss_read")
OPS_LINE = "XLA Ops"
IDLE_OUTSIDE = "host_other"  # a gap while the host was in none of SPANS


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    devices: list  # per chip, its [Op] sorted by start
    spans: list  # [(name, start, end)] of the host spans, sorted
    window: tuple  # (start, end): the first and the last traced loss read
    steps: int = 1  # steps completed in the window


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def _device_index(plane_name: str):
    """i for the plane of chip i, "/device:TPU:i"; None for any other."""
    head = "/device:TPU:"
    digits = plane_name[len(head):]
    if plane_name.startswith(head) and digits.isdigit():
        return int(digits)
    return None


def op_name(event_name: str) -> str:
    """The compiled instruction an op event ran. A TPU trace names the
    event by the instruction's whole text (``%fusion.3 = f32[..] fusion(..)``);
    keep the name alone."""
    return event_name.lstrip("%").split(" ")[0]


def load(path: str, n_devices: int) -> Trace:
    """Ops of chips 0..n_devices-1 and the host spans, on one clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = {}
    spans = []
    for plane in data.planes:
        idx = _device_index(plane.name)
        if idx is not None and idx < n_devices:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[idx] = sorted(
                        (Op(op_name(e.name), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events),
                        key=lambda o: o.start,
                    )
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    missing = [i for i in range(n_devices) if not devices.get(i)]
    if missing:
        raise RuntimeError(f"trace has no '{OPS_LINE}' events for chips "
                           f"{missing}")
    spans.sort(key=lambda s: s[1])
    reads = [s for s in spans if s[0] == "loss_read"]
    if len(reads) < 2:
        raise RuntimeError("trace holds fewer than two loss reads")
    # a step's loss read ends when the chip has finished it (the host sends
    # steps ahead and waits on the oldest), so the window from the first
    # read's end to the last's holds len(reads) - 1 steps of the chip's work
    return Trace([devices[i] for i in range(n_devices)], spans,
                 (reads[0][2], reads[-1][2]), len(reads) - 1)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, a, b):
    return [(max(s, a), min(e, b)) for s, e in intervals
            if min(e, b) > max(s, a)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy(ops, a, b) -> float:
    """Seconds in [a, b] during which some operation runs."""
    return length(clip(union((o.start, o.end) for o in ops), a, b))


def gaps(ops, a, b):
    """The idle intervals in [a, b], in order."""
    out, t = [], a
    for s, e in clip(union((o.start, o.end) for o in ops), a, b):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if b > t:
        out.append((t, b))
    return out


def label(t: float, spans) -> str:
    """The host span running at time t (the innermost, latest started)."""
    name = IDLE_OUTSIDE
    for n, s, e in spans:
        if s > t:
            break
        if e >= t:
            name = n
    return name


def idle_by_span(trace: Trace, device: int = 0):
    """{host span: idle seconds} over the traced window of one chip."""
    a, b = trace.window
    out = {}
    for s, e in gaps(trace.devices[device], a, b):
        n = label(0.5 * (s + e), trace.spans)
        out[n] = out.get(n, 0.0) + (e - s)
    return out
