"""Device time of the train step, put down to the stages the program names.

The program enters each stage of its step in a ``jax.named_scope``
(``src/repro/launch/stages.py``), so every compiled instruction carries the
stage in its ``op_name``: ``jit(step)/fwd_bwd/transpose(jvp())/dot_general``.
The benchmark keeps its own copy of the names and imports nothing of the
program, so that it reads a program from before them too.

A traced operation counts once, for its top-level instruction: one of the
entry computation. An operation inside a loop's body is traced within the
loop's own event, and is counted there. A fusion counts for the stage of
its root, which is the fusion's own ``op_name``; ``straddling`` lists the
fusions whose body holds instructions of more than one stage. An operation
with no ``op_name``, or none of the stages in it (a ``copy`` XLA inserted,
the loss's mean), is ``other``.

``scope_ms`` reads any scope at any depth, loop bodies included, for a
per-layer metric of one mechanism inside a stage (a model's routing or
experts inside ``fwd_bwd``).
"""
from __future__ import annotations

import base64
import hashlib
import re
import sys

import layers

STAGES = ("fwd_bwd", "alpha", "encode", "wire", "decode", "clip",
          "update", "counters")
OTHER = "other"

_ENTRY = re.compile(r"^ENTRY\s+%?([\w.\-]+)", re.MULTILINE)
_METADATA = re.compile(r',?\s*(?<!\w)metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_FRAME_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_NAME = re.compile(r"%[\w.\-]+")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _components(op_name: str) -> list:
    """The path components of an ``op_name`` below ``jit(..)/``."""
    return layers._PREFIX.sub("", op_name).split("/")


def stage_of_name(op_name: str) -> str:
    """The outermost stage component of an ``op_name``, else ``other``."""
    for part in _components(op_name):
        if part in STAGES:
            return part
    return OTHER


def _index(ctx) -> dict:
    """Per instruction its stage and whether it is top-level; the stages
    any instruction carries. Computed once a context."""
    if "_stages" not in ctx:
        instrs = ctx["instrs"]
        entry = ctx.get("entry")
        if entry is None:
            found = _ENTRY.search(ctx.get("hlo_text", ""))
            entry = found.group(1) if found else None
        stage = {n: stage_of_name(i.get("op_name", "")) for n, i in instrs.items()}
        body = {}
        for n, i in instrs.items():
            body.setdefault(i.get("computation"), []).append(n)
        ctx["_stages"] = {
            "entry": entry,
            "stage": stage,
            "body": body,
            "top": set(body.get(entry, ())),
            "carried": set(stage.values()) - {OTHER},
        }
    return ctx["_stages"]


def top_level(ctx, name: str) -> bool:
    """Whether a traced operation is counted: an instruction of the entry
    computation, or one the compiled module does not name."""
    ix = _index(ctx)
    return name in ix["top"] or name not in ix["stage"]


def stage_of(ctx, name: str) -> str:
    """The stage of traced operation `name` (an instruction name)."""
    return _index(ctx)["stage"].get(name, OTHER)


def ms(ctx, names) -> float | None:
    """Device time per step of the top-level operations of the stages
    `names`, averaged over chips. 0.0 where the stages are present in the
    compiled step but XLA fused all their work into another stage's
    operations, and where the step names no stage at all (a program from
    before the names, which has no stage to miss); None where it names
    others but none of these, so that a renamed stage fails the run."""
    ix = _index(ctx)
    if not ix["carried"]:
        if not ix.get("said"):
            print("stages: the compiled step names no stage; each stage "
                  "reads 0.0", file=sys.stderr)
        ix["said"] = True
        return 0.0
    if not ix["carried"] & set(names):
        return None
    value = layers.ms_per_step(
        ctx, lambda c, n: top_level(c, n) and stage_of(c, n) in names)
    return value or 0.0


def scope_ms(ctx, scope: str) -> float | None:
    """Device time per step, averaged over chips, of the traced operations
    whose ``op_name`` has `scope` as a path component, at any depth: a
    stage, or a scope inside one (``checkpoint``, a model's own
    ``jax.named_scope``), loop bodies included. Each operation counts once:
    an event that lies inside another counted event of its chip (a body
    operation inside its loop's event) counts within it. As
    ``layers.ms_per_step`` does, an event counts by its start: a loop that
    began before the window counts not at all, nor do its body's events.
    0.0 where the instructions carry the scope and none ran in the window;
    None where no instruction of the compiled step carries it, so that a
    renamed scope fails the run."""
    named = {n for n, i in ctx["instrs"].items()
             if scope in _components(i.get("op_name", ""))}
    if not named:
        return None
    a, b = ctx["trace"].window
    total = 0.0
    for ops in ctx["trace"].devices:
        end = None
        for o in sorted((o for o in ops if o.name in named),
                        key=lambda o: (o.start, -o.end)):
            if end is not None and o.end <= end:
                continue  # inside the counted event before it
            end = o.end
            if a <= o.start < b:
                total += o.end - o.start
    return total / len(ctx["trace"].devices) / ctx["steps"] * 1e3


def split(ctx) -> dict:
    """{stage: ms per step} for every stage and ``other``; the values add
    up to the time the top-level operations run."""
    out = {s: ms(ctx, (s,)) or 0.0 for s in STAGES}
    out[OTHER] = layers.ms_per_step(
        ctx, lambda c, n: top_level(c, n) and stage_of(c, n) == OTHER) or 0.0
    return out


def _body_stages(ctx, calls: str, seen: set) -> set:
    """The stages of the instructions in computation `calls` and in the
    computations its instructions call."""
    ix, instrs = _index(ctx), ctx["instrs"]
    seen.add(calls)
    out = set()
    for n in ix["body"].get(calls, ()):
        if "op_name" in instrs[n]:
            out.add(ix["stage"][n])
        inner = instrs[n].get("calls")
        if inner and inner not in seen:
            out |= _body_stages(ctx, inner, seen)
    return out - {OTHER}


def straddling(ctx) -> list:
    """[(fusion, its stage, the stages in its body, ms per step)] for the
    top-level fusions whose body holds instructions of more than one
    stage, the slowest first."""
    instrs = ctx["instrs"]
    out = []
    for name in sorted(_index(ctx)["top"]):
        info = instrs[name]
        if info.get("opcode") != "fusion" or not info.get("calls"):
            continue
        inside = _body_stages(ctx, info["calls"], set())
        if len(inside) > 1:
            t = layers.ms_per_step(ctx, lambda c, n, f=name: n == f) or 0.0
            out.append((name, stage_of(ctx, name), sorted(inside), t))
    return sorted(out, key=lambda x: -x[3])


def strip_metadata(hlo_text: str) -> str:
    """Compiled HLO text without what names and places its instructions:
    every ``metadata={...}`` and the stack-frame tables. Two programs that
    differ only in their scopes give the same text."""
    out, skipping = [], False
    for line in hlo_text.splitlines():
        if line.strip() in _FRAME_TABLES:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            out.append(_METADATA.sub("", line))
    return "\n".join(out) + "\n"


def canonical(hlo_text: str) -> str:
    """``strip_metadata``; each Pallas kernel's Mosaic module without its
    source locations, as a digest; and each instruction and computation
    named by the order it first appears in: XLA names a few instructions
    (the results of a call it keeps) after the scope they were made in."""
    text = _KERNEL_BODY.sub(
        lambda m: f'"body":"{_kernel_digest(m.group(1))}"',
        strip_metadata(hlo_text))
    names = {}
    return _NAME.sub(lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                     text)


def _kernel_digest(body_b64: str) -> str:
    """sha256 of a custom call's Mosaic module with its debug info
    stripped: the module carries the source locations of the kernel's
    call, scopes and file paths among them."""
    from jax.extend.mlir import ir, passmanager

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body_b64))
        passmanager.PassManager.parse(
            "builtin.module(strip-debuginfo)").run(module.operation)
        return hashlib.sha256(str(module).encode()).hexdigest()
