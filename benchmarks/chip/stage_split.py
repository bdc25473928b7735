#!/usr/bin/env python3
"""One traced run of a cell, with the step's device time split by stage.

    python3 benchmarks/chip/stage_split.py --workload <cell> --seed <n> \\
        [--seconds <s>] [--out <report.json>] [--hlo-dir <dir>] \\
        [--record <step.json.gz>]

Runs the cell as ``run.py --trace 1`` does (``run.run_cell``) and prints
run.py's result line. Then, as one more line of JSON (and in ``--out``):
per traced step, the device time of every stage and of ``other``
(``stages.split``), their sum beside the chip's busy time, the fusions
that hold more than one stage (``stages.straddling``), and the sha256 of
the compiled ``exact`` and ``compressed`` programs with their metadata
stripped (``stages.strip_metadata``, ``stages.canonical``), which two
checkouts that differ only in their scopes share. ``--hlo-dir`` keeps
those stripped texts; ``--record`` keeps the first traced step of chip 0
as a test fixture (``tests/data/granite_step.json.gz``).
"""
from __future__ import annotations

import argparse
import gzip
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402
import layers  # noqa: E402
import run  # noqa: E402
import stages  # noqa: E402
import trace_reduce as tr  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float,
                    default=bench.benchmark()["run_seconds"])
    ap.add_argument("--out")
    ap.add_argument("--hlo-dir")
    ap.add_argument("--record")
    return ap.parse_args(argv)


def digests(prog, first: int, hlo_dir, cell_name: str) -> dict:
    """sha256 of the stripped compiled programs the run drove."""
    out = {}
    for which, i in (("exact", 0), ("compressed", first)):
        text = layers.compiled_text(prog, i)
        forms = {"stripped": stages.strip_metadata(text),
                 "canonical": stages.canonical(text)}
        out[which] = {k: hashlib.sha256(v.encode()).hexdigest()
                      for k, v in forms.items()}
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            path = os.path.join(hlo_dir, f"{cell_name}.{which}.hlo.gz")
            with gzip.open(path, "wt") as f:
                f.write(forms["stripped"])
    return out


def split_report(ctx) -> dict:
    """Per traced step: each stage's ms, their sum, and the busy ms."""
    trace = ctx["trace"]
    a, b = trace.window
    busy = sum(tr.busy(ops, a, b) for ops in trace.devices)
    busy_ms = busy / len(trace.devices) / ctx["steps"] * 1e3
    split = stages.split(ctx)
    return {
        "steps": ctx["steps"],
        "stages_ms": split,
        "sum_ms": sum(split.values()),
        "busy_ms": busy_ms,
        "straddling": [list(x) for x in stages.straddling(ctx)],
    }


def fixture(ctx, source: str) -> dict:
    """Chip 0's first traced step: its ops (instruction name, start and end
    in ns from the step's start), and the compiled instructions that are
    traced, in the entry computation, or in the fusions it calls."""
    trace = ctx["trace"]
    reads = [s for s in trace.spans if s[0] == "loss_read"]
    a, b = trace.window[0], reads[1][2]
    ops = [[o.name, round((o.start - a) * 1e9), round((o.end - a) * 1e9)]
           for o in trace.devices[0] if a <= o.start < b]
    instrs = ctx["instrs"]
    ix = stages._index(ctx)
    keep = {o[0] for o in ops} & set(instrs)
    todo = list(ix["top"])
    while todo:
        n = todo.pop()
        keep.add(n)
        inner = instrs[n].get("calls")
        if inner:
            todo += [m for m in ix["body"].get(inner, ()) if m not in keep]
    rows = [[n, i["opcode"], i.get("computation"), i.get("op_name"),
             i.get("calls")] for n, i in sorted(instrs.items()) if n in keep]
    return {"source": source, "entry": ix["entry"],
            "window_ns": [0, round((b - a) * 1e9)], "steps": 1,
            "ops": ops, "instrs": rows}


def main(argv=None) -> int:
    args = parse(argv)
    cell = bench.load_cell(args.workload)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    device = run.device_info(cell.chips)
    report = {"workload": cell.name, "seed": args.seed}

    def hook(prog):
        free = prog.free

        def free_after_digests():
            report["hlo"] = digests(prog, cell.traffic["check_steps"],
                                    args.hlo_dir, cell.name)
            free()

        prog.free = free_after_digests

    read_all = layers.read_all

    def read_all_and_split(c, ctx):
        report.update(split_report(ctx))
        if args.record:
            fx = fixture(ctx, f"one traced step of {cell.name} on "
                              f"{device['kind']}, chip 0 (stage_split.py)")
            with gzip.open(args.record, "wt") as f:
                json.dump(fx, f, separators=(",", ":"))
            report["recorded"] = {"path": args.record,
                                  "bytes": os.path.getsize(args.record),
                                  "ops": len(fx["ops"]),
                                  "instrs": len(fx["instrs"])}
        return read_all(c, ctx)

    layers.read_all = read_all_and_split
    result = run.run_cell(cell, args.seed, args.seconds, True, device=device,
                          program_hook=hook)
    run.emit(result)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print("stage_split " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
