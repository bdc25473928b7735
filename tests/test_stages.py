"""The train step's stage names (launch/stages.py) in the compiled program,
and the trainer's host spans in a recorded profile."""
import contextlib
import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ShapeConfig, get_arch, smoke_config
from repro.core import make_compressor
from repro.launch import stages
from repro.launch.step import build_train_step
from repro.optim import sgd
from repro.optim.schedules import constant

ALL = set(stages.STAGES)
# (route, program, microbatches) -> the stages that route runs; the fused
# route decodes inside its kernel, the exact step sends floats
ROUTES = {
    ("zero1", "exact", 1): {"fwd_bwd", "wire", "clip", "update", "alpha"},
    ("zero1", "compressed", 1): ALL,
    ("zero1", "exact", 2): {"fwd_bwd", "wire", "clip", "update", "alpha"},
    ("zero1", "compressed", 2): ALL,
    ("fused", "exact", 1): {"fwd_bwd", "wire", "clip", "update", "alpha"},
    ("fused", "compressed", 1): ALL - {"decode"},
}
CASES = sorted(ROUTES)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.+?\s([a-z][\w\-]*)\(")
_OPNAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_METADATA = re.compile(r',?\s*(?<!\w)metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_FRAME_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_NAME = re.compile(r"%[\w.\-]+")


def _compiled_text(route, program, microbatches):
    cfg = smoke_config(get_arch("granite-8b"))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    art = build_train_step(
        cfg, mesh, ShapeConfig("t", 16, 4, "train"),
        compressor=make_compressor("intsgd8"),
        base_opt=sgd(momentum=0.9, weight_decay=1e-4),
        lr_schedule=constant(0.1), param_dtype=jnp.float32,
        fused=route == "fused", wire="packed8", clip_norm=1.0,
        microbatches=microbatches,
    )
    return art.jitted[program].lower(*art.arg_structs).compile().as_text()


@functools.lru_cache(maxsize=None)
def _scoped(case):
    return _compiled_text(*case)


def _instructions(text):
    """[(opcode, op_name or '')] of every instruction in the module."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            o = _OPNAME.search(line)
            out.append((m.group(1), o.group(1) if o else ""))
    return out


def _stage_parts(op_name):
    return [p for p in op_name.split("/") if p in ALL]


def _strip(text):
    """The text without metadata and without the stack-frame tables, each
    instruction and computation named by the order it first appears in:
    XLA names a few instructions (the results of a call it keeps) after
    the scope they were made in."""
    out, skipping = [], False
    for line in text.splitlines():
        if line.strip() in _FRAME_TABLES:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            out.append(_METADATA.sub("", line))
    names = {}
    return _NAME.sub(lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                     "\n".join(out))


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_every_stage_the_route_runs_is_named(case):
    named = {p for _, o in _instructions(_scoped(case)) for p in _stage_parts(o)}
    assert named == ROUTES[case]


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_every_matmul_is_forward_or_backward(case):
    dots = [o for op, o in _instructions(_scoped(case))
            if op in ("dot", "convolution")]
    assert dots
    assert all(_stage_parts(o) == ["fwd_bwd"] for o in dots), [
        o for o in dots if _stage_parts(o) != ["fwd_bwd"]][:5]


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_stages_never_nest(case):
    nested = [o for _, o in _instructions(_scoped(case))
              if len(_stage_parts(o)) > 1]
    assert not nested, nested[:5]


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_scopes_change_no_instruction(case, monkeypatch):
    scoped = _scoped(case)
    monkeypatch.setattr(stages, "stage", lambda name: contextlib.nullcontext())
    bare = _compiled_text(*case)
    assert any(_stage_parts(o) for _, o in _instructions(scoped))
    assert not any(_stage_parts(o) for _, o in _instructions(bare))
    assert _strip(bare) == _strip(scoped)


def test_unknown_stage_is_refused():
    with pytest.raises(ValueError, match="unknown stage"):
        stages.stage("forward")
    with pytest.raises(ValueError, match="unknown stage"):
        stages.scoped("optimizer")


def test_trainer_profile_holds_its_host_spans_per_step(tmp_path, capsys):
    from jax.profiler import ProfileData

    from repro.launch.train import PROFILE_FIRST, PROFILE_STEPS, train_loop

    cfg = smoke_config(get_arch("xlstm-125m"))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    steps = PROFILE_FIRST + PROFILE_STEPS
    _, losses = train_loop(
        cfg, mesh, ShapeConfig("t", 16, 2, "train"), compressor="intsgd8",
        wire="packed8", steps=steps, log_every=1, profile_dir=str(tmp_path),
    )
    assert len(losses) == steps
    dts = re.findall(r"dt (\d+\.\d)ms", capsys.readouterr().out)
    assert len(dts) == steps and all(float(d) > 0 for d in dts)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted(
        (e.start_ns, e.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:CPU")
        for line in plane.lines for e in line.events
        if e.name in ("data", "step", "loss_read", "checkpoint")
    )
    assert [n for _, n in spans] == ["data", "step", "loss_read"] * PROFILE_STEPS
