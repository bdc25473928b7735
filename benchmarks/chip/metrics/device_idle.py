"""device_idle (device, %): the share of the traced window in which no
operation runs on a chip, 1 - union(op intervals) / window; the worst chip."""

import trace_reduce as tr


def read(ctx):
    t = ctx["trace"]
    a, b = t.window
    return max(100.0 * (1.0 - tr.busy(ops, a, b) / (b - a)) for ops in t.devices)
