#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at its own size.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 21,22,23 [--out FILE]

For every seed of ``--seeds``, the program's first steps, taken as a run
takes them in set-up, against the reference: the lower readings. For every
seed of ``--control-seeds``, in the program's place: the control (the
reference with its matrix products in float8, one precision below the
bfloat16 the configuration computes in), and the faults a training cell can
have, planted in the reference: half of the batch left out, and on more
than one worker the exchange left out (``no_exchange``: each worker decodes
only its own integers). A state left unchanged reads 1 on ``change`` and
needs no run. One process compiles the program once for all seeds. Prints
one JSON line per reading and a summary line (the largest lower reading
and the smallest reading of each kind, per number); ``--out`` writes them
to a file too.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402
import run  # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    try:
        run.device_info(cell.chips)
    except run.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(calibrate(cell, args.seeds, args.control_seeds,
                               out=args.out)), flush=True)
    return 0


def calibrate(cell, lower_seeds, control_seeds, *, out=None):
    import check
    from program import Program
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    lines = []

    def record(kind, seed, nums, t0, got=None, ref=None):
        line = dict(kind=kind, seed=seed, seconds=time.perf_counter() - t0,
                    **nums)
        if got is not None:  # the look behind a worst-leaf number
            line["leaves"] = {
                "grad0": check.leaf_gaps(got["grad0"], ref["grad0"]),
                "change": check.leaf_gaps(got["change"], ref["change"]),
                "loss_steps": [abs(p - r) / abs(r) for p, r in
                               zip(got["losses"], ref["losses"])],
            }
        lines.append(line)
        print(json.dumps(line), flush=True)

    faults = ("float8", "half_batch") + (
        ("no_exchange",) if cell.traffic["data_parallel"] > 1 else ())
    prog = Program(cell.config, cell.traffic, cell.chips, lower_seeds[0])
    batches = {}
    for seed in lower_seeds + [s for s in control_seeds
                               if s not in lower_seeds]:
        t0 = time.perf_counter()
        prog.reseed(seed)
        prog.start()
        readings = prog.first_steps(cell.traffic["check_steps"])
        prog.free()
        gc.collect()
        batches[seed] = readings["batches"]
        ref = check.reference_readings(cell, seed, readings["batches"])
        if seed in lower_seeds:
            record("program", seed, check.numbers(readings, ref), t0,
                   readings, ref)
            if len(lines) == 1:
                print(json.dumps({"leaf_names": ref["leaves"]}), flush=True)
        if seed not in control_seeds:
            continue
        for kind in faults:
            t0 = time.perf_counter()
            got = check.reference_readings(
                cell, seed, readings["batches"],
                precision="float8" if kind == "float8" else "float32",
                fault=None if kind == "float8" else kind)
            record(kind, seed, check.numbers(got, ref), t0, got, ref)
    summary = {"kind": "summary"}
    for kind in sorted({x["kind"] for x in lines}):
        rows = [x for x in lines if x["kind"] == kind]
        pick = max if kind == "program" else min
        summary[kind] = {n: pick(x[n] for x in rows)
                         for n in ("loss", "grad0", "change", "change_median")}
        summary[kind]["seeds"] = len(rows)
    if out:
        with open(out, "w") as f:
            for x in lines + [summary]:
                f.write(json.dumps(x) + "\n")
    return summary


if __name__ == "__main__":
    sys.exit(main())
