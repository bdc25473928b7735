"""From a traced run to the per-layer metrics and the breakdown.

``context`` gathers what a metric's reader may read: the reduced trace,
the compiled step's instructions (to tell a trace's operations apart by
what they are: a collective with its element type, a Pallas kernel by its
name), the number of traced steps, the rate of the steps outside the trace,
the cell, and the chip's peaks. ``read_all`` calls the reader of every
per-layer metric the cell reports. A reader returns None where it finds
nothing; for a metric the cell declares, that fails the run, so that a
renamed kernel or trace plane cannot silently drop a metric.
"""
from __future__ import annotations

import re

import bench
import hlo
import trace_reduce as tr

TOP = 10  # entries of each breakdown list


class MetricMissing(RuntimeError):
    """A per-layer metric the cell declares could not be read."""
_PREFIX = re.compile(r"^jit\([^)]*\)/(shard_map/)?")


def compiled_text(prog, i: int) -> str:
    """The compiled HLO of the program the window drives, lowered for step
    i's own arguments, so that it is the program the persistent cache
    already holds and is loaded, not compiled again."""
    return prog.lowered(i).compile().as_text()


def context(cell, path: str, text: str, *, tokens_per_s: float) -> dict:
    import jax

    trace = tr.load(path, cell.chips)
    return {
        "hlo_text": text,
        "trace": trace,
        "instrs": hlo.instructions(text),
        "steps": trace.steps,
        "tokens_per_s": tokens_per_s,
        "cell": cell,
        "chips": cell.chips,
        "peaks": bench.peaks(jax.devices()[0].device_kind),
        "cost": bench.cost,
    }


# ---------------------------------------------------------------------------
# helpers the readers share
# ---------------------------------------------------------------------------
def kernel_of(ctx, op_name: str):
    info = ctx["instrs"].get(op_name) or {}
    if info.get("target") == "tpu_custom_call":
        return info.get("kernel", "tpu_custom_call")
    return None


def collective_of(ctx, op_name: str):
    return hlo.collective_of(op_name, ctx["instrs"])


def ops_where(ctx, pred):
    """Per chip, the traced operations for which pred(ctx, name) holds,
    inside the traced window."""
    a, b = ctx["trace"].window
    return [[o for o in ops if a <= o.start < b and pred(ctx, o.name)]
            for ops in ctx["trace"].devices]


def ms_per_step(ctx, pred):
    """Device time of the matching operations, per traced step, averaged
    over the chips; None where no operation matches."""
    per_chip = ops_where(ctx, pred)
    if not any(per_chip):
        return None
    total = sum(sum(o.end - o.start for o in ops) for ops in per_chip)
    return total / len(per_chip) / ctx["steps"] * 1e3


def stable_name(ctx, op_name: str) -> str:
    """What an operation is, in words that survive a recompile: a kernel's
    name, a collective's kind and element type, or the source-level op
    name the compiler kept for it."""
    k = kernel_of(ctx, op_name)
    if k:
        return f"pallas:{k}"
    c = collective_of(ctx, op_name)
    if c:
        return f"{c[0]}:{c[1]}"
    info = ctx["instrs"].get(op_name) or {}
    src = _PREFIX.sub("", info.get("op_name", ""))
    return src[:96] or info.get("opcode", op_name.split(".")[0])


# ---------------------------------------------------------------------------
# the whole reading
# ---------------------------------------------------------------------------
def breakdown(ctx) -> dict:
    trace = ctx["trace"]
    a, b = trace.window
    by_name = {}
    for ops in trace.devices:
        for o in ops:
            if a <= o.start < b:
                n = stable_name(ctx, o.name)
                by_name[n] = by_name.get(n, 0.0) + (o.end - o.start)
    n_chips = len(trace.devices)
    device_ops = sorted(((n, s / n_chips) for n, s in by_name.items()),
                        key=lambda x: -x[1])[:TOP]
    idle = {}
    for d in range(n_chips):
        for n, s in tr.idle_by_span(trace, d).items():
            idle[n] = idle.get(n, 0.0) + s / n_chips
    idle_gaps = sorted(idle.items(), key=lambda x: -x[1])[:TOP]
    return {"device_ops": [list(x) for x in device_ops],
            "idle_gaps": [list(x) for x in idle_gaps]}


def read_all(cell, ctx):
    """(per-layer metrics, breakdown, busy_s, window_s) of a traced run."""
    trace = ctx["trace"]
    a, b = trace.window
    busy = [tr.busy(ops, a, b) for ops in trace.devices]
    metrics = {}
    for m in cell.per_layer:
        value = bench.reader(m["name"])(ctx)
        if value is None:
            a, b = trace.window
            seen = sorted({o.name for ops in trace.devices for o in ops
                           if a <= o.start < b})
            kernels = sorted({i.get("kernel", "?") for i in ctx["instrs"].values()
                              if i.get("target") == "tpu_custom_call"})
            raise MetricMissing(
                f"{m['name']}: nothing to read in the trace of {cell.name}; "
                f"{len(seen)} op names traced, e.g. {seen[:20]}; Pallas "
                f"kernels in the compiled step: {kernels}")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return (metrics, breakdown(ctx), sum(busy) / len(busy), b - a)
