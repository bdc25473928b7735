#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic and limits are files of their own (see ``bench``).
A run drives the trainer's own step on a TPU and nothing else:

  set-up   weights made on the device from the seed, the state built, and
           the first ``check_steps`` steps driven through the window's own
           call and feed (step 0 on the program the traffic names, the rest
           on ``compressed``); the check's readings of the program are
           taken there. ``setup_s`` is process start to the first timed
           step.
  window   steps for ``--seconds`` on ``POOL`` batches made before it
           starts, each sent about ``AHEAD_S`` seconds of steps before the
           host reads its loss, so that a stall of the host does not idle
           the chip; when the time is up nothing more is sent and every
           step sent is waited for and counted. A step's
           time is the interval between its loss read and the previous
           one. No program may compile in the window.
  --trace 1  the same, with the profiler on for a few steps in the middle
           of the window; the per-layer metrics are read from that trace
           and ``mfu`` from the steps outside it.
  check    after the window, with the program's memory released: the plain
           reference (``reference.py``) runs the same first steps on the
           same batches, and every compared number is printed beside its
           limit. ``correct`` is whether all are within.

The last line of standard output is one JSON object. Off a TPU, or with
fewer chips than the cell asks for, the run exits with code 2 and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402

# the TPU runtime's own logs would go to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402

# losses read under the profiler, by chips; the traced window spans one step
# fewer. Keeps the trace small.
PROFILED_STEPS = {1: 5, 4: 3}
AHEAD_S = 8.0  # seconds of steps sent ahead of the loss the host waits for
POOL = 32  # batches made in set-up, which the window's steps cycle through


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCounter:
    """Counts programs lowered or compiled while it is armed."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in self.EVENTS:
            self.count += 1


def percentile(values, q):
    """The q-th percentile, linear between order statistics."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def peak_memory(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def window(prog, feed: list, first: int, seconds: float, ahead: int, *,
           log_dir=None, n_prof: int = 4) -> dict:
    """Steps from `first` on for `seconds`, each sent `ahead` steps before
    its loss is read, so that the chip keeps working while the host stands
    still. The steps cycle through the batches of `feed`, made from the
    seed in set-up. When the time is up nothing more is sent, and every step sent is
    waited for and counted. With `log_dir`, the profiler records from the
    middle of the window until `n_prof` more losses have been read.

    Returns the start, each step's completion time (its loss read), the
    losses, and (a, b): the first and the last loss read under the
    profiler, as indices into the completions."""
    import jax

    pending, done, losses = collections.deque(), [], []
    profiled, tracing = None, False
    i = first
    start = time.perf_counter()

    def read_oldest():
        nonlocal profiled, tracing
        losses.append(prog.read(pending.popleft()))
        done.append(time.perf_counter())
        if tracing and len(done) - profiled[0] == n_prof:
            jax.profiler.stop_trace()
            tracing, profiled = False, (profiled[0], len(done) - 1)

    while time.perf_counter() - start < seconds:
        if (log_dir and profiled is None
                and time.perf_counter() - start >= seconds / 2):
            jax.profiler.start_trace(log_dir)
            tracing, profiled = True, (len(done), None)
        pending.append(prog.dispatch(i, feed[(i - first) % len(feed)]))
        i += 1
        if len(pending) > ahead:
            read_oldest()
    while pending:
        read_oldest()
    if tracing:
        jax.profiler.stop_trace()
        profiled = (profiled[0], len(done) - 1)
    return {"start": start, "done": done, "losses": losses,
            "profiled": profiled}


def run_cell(cell: bench.Cell, seed: int, seconds: float, trace: bool, *,
             device: dict, program_hook=None) -> dict:
    """One run of `cell`: set-up, window, check. Returns the result dict.
    ``program_hook(program)`` may replace the program's step for a test
    that breaks the timed path."""
    import jax

    from program import Program
    import check
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()

    # -- set-up: build, make the weights, drive the first steps -------------
    prog = Program(cell.config, cell.traffic, cell.chips, seed)
    if program_hook is not None:
        program_hook(prog)
    prog.start()
    readings = prog.first_steps(cell.traffic["check_steps"])
    first = cell.traffic["check_steps"]
    feed = prog.batches(first, POOL)
    setup_s = time.perf_counter() - T0

    # -- the window -----------------------------------------------------------
    ahead = max(1, math.ceil(AHEAD_S / readings["step_s"][-1]))
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    counter.armed = True
    w = window(prog, feed, first, seconds, ahead,
               log_dir=log_dir, n_prof=PROFILED_STEPS.get(cell.chips, 3))
    counter.armed = False
    done = w["done"]
    window_s = done[-1] - w["start"]
    steps = len(done)
    tokens = steps * prog.tokens_per_step
    step_s = [b - a for a, b in zip(done, done[1:])]
    losses = w["losses"]
    memory = peak_memory(cell.chips)

    result = {
        "attempted": steps,
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "device": dict(device, memory_peak_bytes=memory),
    }
    e2e = {
        "tokens_per_s": (tokens / window_s, "tokens/s"),
        "step_ms_p90": (percentile(step_s, 90) * 1e3, "ms"),
        "setup_s": (setup_s, "s"),
    }
    med = statistics.median(step_s)
    extra = {"window_s": window_s, "steps": steps, "ahead": ahead,
             "step_ms_max": max(step_s) * 1e3,
             "steps_over_1.5x_median": sum(1 for x in step_s if x > 1.5 * med),
             "step_ms_median": statistics.median(step_s) * 1e3,
             "window_compiles": counter.count}
    if trace:
        import layers
        import trace_reduce as tr

        a, b = w["profiled"]  # the loss reads done[a] .. done[b] were traced
        traced_s = done[b] - (done[a - 1] if a else w["start"])
        xplane = tr.xplane_file(log_dir)
        ctx = layers.context(
            cell, xplane, layers.compiled_text(prog, first + steps),
            tokens_per_s=((steps - (b - a + 1)) * prog.tokens_per_step
                          / (window_s - traced_s)),
        )
        shutil.rmtree(log_dir, ignore_errors=True)
        per_layer, breakdown, busy_s, win = layers.read_all(cell, ctx)
        result["metrics"] = per_layer
        result["breakdown"] = breakdown
        result["device"].update(busy_s=busy_s, window_s=win)
        extra["steps_traced"] = ctx["steps"]
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    extra.update({k: v[0] for k, v in e2e.items()})

    # -- the check, with the program's memory released ------------------------
    prog.free()
    del prog
    gc.collect()
    checks = check.compare(cell, seed, readings, counter.count)
    result["correct"] = all(c["ok"] for c in checks.values())
    result["extra"] = extra
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def emit(result: dict) -> None:
    """The checks as the last lines of standard error, and the result as
    the last line of standard output, with the checks last in it."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["extra"] = result["extra"]
    out["checks"] = result["checks"]
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = bench.load_cell(args.workload)
        sys.path.insert(0, os.path.join(bench.ROOT, "src"))
        device = device_info(cell.chips)
        bench.peaks(device["kind"])
        import program  # noqa: F401  (the system under test must be there)
    except (bench.BenchmarkError, NoChip, ImportError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device=device)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
