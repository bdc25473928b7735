"""The stage readers (stages.py, metrics/*_ms.py) on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

On steps recorded on the chip by ``stage_split.py --record`` (granite-8b on
one chip, ``data/granite_step.json.gz``; danube, whose two layers run in a
loop, ``data/danube_stages.json.gz``; granite on four chips, chip 0,
``data/granite_dp4_step.json.gz``) and on events laid out by hand.
"""
import copy
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, HERE]

import bench  # noqa: E402
import layers  # noqa: E402
import stages  # noqa: E402
import tiny  # noqa: E402
import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "data", "granite_step.json.gz")
FIXTURES = {"granite": RECORDED,
            "danube": os.path.join(HERE, "data", "danube_stages.json.gz"),
            "granite_dp4": os.path.join(HERE, "data", "granite_dp4_step.json.gz")}
READERS = {"fwd_bwd_ms": ("fwd_bwd",), "alpha_ms": ("alpha",),
           "encode_ms": ("encode",), "wire_ms": ("wire",),
           "update_ms": ("decode", "clip", "update")}


def _recorded(path=RECORDED):
    """A context as the harness builds it, from a recorded step."""
    with gzip.open(path, "rt") as f:
        fx = json.load(f)
    ops = sorted((tr.Op(n, s * 1e-9, e * 1e-9) for n, s, e in fx["ops"]),
                 key=lambda o: o.start)
    instrs = {}
    for name, opcode, comp, op_name, calls in fx["instrs"]:
        info = {"opcode": opcode, "arrays": [], "computation": comp}
        if op_name is not None:
            info["op_name"] = op_name
        if calls is not None:
            info["calls"] = calls
        instrs[name] = info
    trace = tr.Trace([ops], [], tuple(w * 1e-9 for w in fx["window_ns"]),
                     fx["steps"])
    return {"trace": trace, "instrs": instrs, "steps": fx["steps"],
            "entry": fx["entry"]}


def _read(ctx, metric):
    return bench.reader(metric)(ctx)


def _declared(*names):
    cell = tiny.cell()
    per_layer = tuple(m for m in bench.benchmark()["per_layer"]
                      if m["name"] in names)
    return bench.Cell(**dict(cell.__dict__, per_layer=per_layer))


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------
def test_the_benchmarks_stage_names_are_the_programs():
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    from repro.launch import stages as program_stages

    assert stages.STAGES == program_stages.STAGES


@pytest.mark.parametrize("op_name, stage", [
    ("jit(step)/fwd_bwd/transpose(jvp())/dot_general", "fwd_bwd"),
    ("jit(step)/shard_map/update/jit(fused_unpack_apply)/pallas_call", "update"),
    ("jit(step)/encode/jit(clip)/min", "encode"),
    ("jit(step)/jit(clip)/min", "other"),
    ("jit(step)/fwd_bwd/jvp(encode)/mul", "fwd_bwd"),
    ("", "other"),
])
def test_stage_of_an_op_name_is_its_outermost_stage(op_name, stage):
    assert stages.stage_of_name(op_name) == stage


def test_stripped_text_drops_names_and_places_only():
    text = (
        "HloModule m\n\nFileNames\n1 \"a.py\"\n\nStackFrames\n1 {x=1}\n\n"
        "ENTRY %main.1 (p: f32[2]) -> f32[2] {\n"
        "  %p = f32[2]{0} parameter(0)\n"
        "  %encode.3 = f32[2]{0} add(%p, %p), metadata={op_name=\"jit(f)/"
        "encode/add\" source_file=\"a.py\" source_line=3}\n"
        "  ROOT %m = f32[2]{0} multiply(%encode.3, %p), metadata={op_name="
        "\"x{}\\\"y\"}\n}\n")
    renamed = text.replace("%encode.3", "%jit_f_.3").replace("encode/add", "add")
    assert "metadata" not in stages.strip_metadata(text)
    assert "FileNames" not in stages.strip_metadata(text)
    assert stages.strip_metadata(text) != stages.strip_metadata(renamed)
    assert stages.canonical(text) == stages.canonical(renamed)
    other = text.replace("multiply(", "subtract(")
    assert stages.canonical(text) != stages.canonical(other)


# ---------------------------------------------------------------------------
# events laid out by hand
# ---------------------------------------------------------------------------
def _hand_ctx(op_names):
    """One entry computation whose instructions carry `op_names`; each runs
    1 ms, and a loop body instruction runs inside the first."""
    instrs = {f"i{k}": {"opcode": "fusion", "arrays": [], "computation": "main",
                        "op_name": o} for k, o in enumerate(op_names)}
    instrs["inner"] = {"opcode": "add", "arrays": [], "computation": "body",
                       "op_name": op_names[0] + "/body/add"}
    ops = [tr.Op(f"i{k}", k * 1e-3, (k + 1) * 1e-3) for k in range(len(op_names))]
    ops.append(tr.Op("inner", 0.2e-3, 0.4e-3))
    trace = tr.Trace([sorted(ops, key=lambda o: o.start)], [],
                     (0.0, len(op_names) * 1e-3))
    return {"trace": trace, "instrs": instrs, "steps": 1, "entry": "main"}


def test_a_loop_body_counts_within_its_loop():
    ctx = _hand_ctx(["jit(step)/fwd_bwd/while", "jit(step)/update/mul",
                     "jit(step)/decode/div", "jit(step)/copy"])
    assert _read(ctx, "fwd_bwd_ms") == pytest.approx(1.0)
    assert _read(ctx, "update_ms") == pytest.approx(2.0)
    split = stages.split(ctx)
    assert split["other"] == pytest.approx(1.0)
    assert sum(split.values()) == pytest.approx(4.0)


def test_a_program_without_stage_names_reads_zero():
    ctx = _hand_ctx(["jit(step)/while", "jit(step)/mul"])
    for metric in READERS:
        assert _read(ctx, metric) == 0.0
    assert stages.split(ctx)["other"] == pytest.approx(2.0)


def test_a_scope_counts_each_operation_once():
    """A loop's event holds its body's events; a scope counts an event once,
    whether it matched the loop, the body or both, on each chip."""
    named = {
        "loop": ("main", "jit(step)/fwd_bwd/while"),
        "b1": ("body", "jit(step)/fwd_bwd/while/body/checkpoint/dot_general"),
        "b2": ("body", "jit(step)/fwd_bwd/while/body/checkpoint/add"),
        "b3": ("body", "jit(step)/fwd_bwd/while/body/mul"),
        "top": ("main", "jit(step)/fwd_bwd/checkpoint/exp"),
        "upd": ("main", "jit(step)/shard_map/update/mul"),
        "idle": ("main", "jit(step)/fwd_bwd/unused/neg"),
    }
    instrs = {n: {"opcode": "fusion", "arrays": [], "computation": comp,
                  "op_name": o} for n, (comp, o) in named.items()}
    ms = [("loop", 0, 3), ("b1", 0.5, 1), ("b2", 1, 1.5), ("b3", 2, 2.5),
          ("top", 3, 4), ("upd", 4, 5)]
    ops = [tr.Op(n, s * 1e-3, e * 1e-3) for n, s, e in ms]
    trace = tr.Trace([ops, ops], [], (0.0, 5e-3))
    ctx = {"trace": trace, "instrs": instrs, "steps": 1, "entry": "main"}
    assert stages.scope_ms(ctx, "fwd_bwd") == pytest.approx(4.0)
    assert stages.scope_ms(ctx, "while") == pytest.approx(3.0)
    assert stages.scope_ms(ctx, "checkpoint") == pytest.approx(2.0)
    assert stages.scope_ms(ctx, "update") == pytest.approx(1.0)
    assert stages.scope_ms(ctx, "unused") == 0.0
    assert stages.scope_ms(ctx, "nosuch") is None
    assert stages.scope_ms(ctx, "fwd_bwd") == pytest.approx(
        stages.ms(ctx, ("fwd_bwd",)))


# ---------------------------------------------------------------------------
# one granite step recorded on the chip
# ---------------------------------------------------------------------------
def _relabel(ctx, stage, to, top_only):
    """A copy of the context whose instructions of `stage` (the top-level
    ones only, or all) carry stage `to` instead."""
    ctx = {k: v for k, v in ctx.items() if k != "_stages"}
    ctx["instrs"] = copy.deepcopy(ctx["instrs"])
    top = stages._index(dict(ctx))["top"]
    for name, info in ctx["instrs"].items():
        if stages.stage_of_name(info.get("op_name", "")) == stage and (
                name in top or not top_only):
            info["op_name"] = info["op_name"].replace(f"/{stage}/", f"/{to}/")
    return ctx


def test_recorded_stages_add_up_to_the_busy_time():
    ctx = _recorded()
    a, b = ctx["trace"].window
    busy_ms = tr.busy(ctx["trace"].devices[0], a, b) * 1e3
    split = stages.split(ctx)
    assert set(split) == set(stages.STAGES) | {stages.OTHER}
    assert sum(split.values()) == pytest.approx(busy_ms, rel=1e-3)
    by_reader = {m: _read(ctx, m) for m in READERS}
    assert by_reader["update_ms"] == pytest.approx(
        split["decode"] + split["clip"] + split["update"])
    assert sum(by_reader.values()) + split["counters"] + split["other"] == (
        pytest.approx(busy_ms, rel=1e-3))


def test_recorded_forward_backward_is_the_largest_stage():
    split = stages.split(_recorded())
    assert max(split, key=split.get) == "fwd_bwd"
    assert split["fwd_bwd"] > 0.4 * sum(split.values())


def test_recorded_stage_fused_into_others_reads_zero():
    ctx = _recorded()
    # granite's decode is present, but XLA fused all of it into the clip's
    # and the update's fusions
    assert "decode" in stages._index(ctx)["carried"]
    assert stages.ms(ctx, ("decode",)) == 0.0
    assert any("decode" in inside for _, _, inside, _ in stages.straddling(ctx))
    # the same for encode, once its top-level operations are named wire
    fused = _relabel(ctx, "encode", "wire", top_only=True)
    assert "encode" in stages._index(fused)["carried"]
    assert _read(fused, "encode_ms") == 0.0
    assert _read(fused, "wire_ms") == pytest.approx(
        _read(ctx, "wire_ms") + _read(ctx, "encode_ms"))


def test_recorded_stage_absent_from_the_program_fails_the_run():
    ctx = _relabel(_recorded(), "alpha", "update", top_only=False)
    assert _read(ctx, "alpha_ms") is None
    assert _read(ctx, "fwd_bwd_ms") > 0
    with pytest.raises(layers.MetricMissing, match="alpha_ms"):
        layers.read_all(_declared("fwd_bwd_ms", "alpha_ms"), ctx)


def test_recorded_straddling_lists_each_fusion_once():
    ctx = _recorded()
    found = stages.straddling(ctx)
    names = [name for name, _, _, _ in found]
    assert names and len(names) == len(set(names))
    top = stages._index(ctx)["top"]
    for name, stage, inside, ms in found:
        assert name in top and ctx["instrs"][name]["opcode"] == "fusion"
        assert len(inside) > 1 and stage == stages.stage_of(ctx, name)
        assert ms >= 0
    assert [ms for *_, ms in found] == sorted((ms for *_, ms in found),
                                              reverse=True)


# stages.split of each recorded step, pinned: a change to the stage readers
# must leave these readings as they are
RECORDED_SPLIT = {
    "granite": {
        "fwd_bwd": 116.699567, "alpha": 19.82236600000001,
        "encode": 9.969351999999972, "wire": 7.782919999999985,
        "decode": 0.0, "clip": 4.689072000000044,
        "update": 43.39008800000005, "counters": 4.8066189999999285,
        "other": 21.15027500000001},
    "danube": {
        "fwd_bwd": 76.15105000000005, "alpha": 5.759073000000003,
        "encode": 5.259016999999977, "wire": 17.14379399999999,
        "decode": 0.0, "clip": 0.0, "update": 13.613047999999988,
        "counters": 3.3885770000000175, "other": 26.018367000000065},
    "granite_dp4": {
        "fwd_bwd": 114.86828200000005, "alpha": 6.320275000000014,
        "encode": 10.75403799999998, "wire": 21.780076000000008,
        "decode": 0.0, "clip": 2.332055000000027,
        "update": 33.29943899999996, "counters": 2.1364379999999903,
        "other": 43.873319000000066},
}


@pytest.mark.parametrize("fixture", sorted(FIXTURES))
def test_recorded_stage_readings_are_unchanged(fixture):
    assert stages.split(_recorded(FIXTURES[fixture])) == RECORDED_SPLIT[fixture]


def _outermost_ms(ctx, scope, edge=False):
    """By brute force: the time of chip 0's events of `scope` that lie in
    no other event of it; with `edge`, only the body events among them that
    lie in no top-level event either."""
    ops = ctx["trace"].devices[0]
    named = [o for o in ops if scope in stages._components(
        ctx["instrs"].get(o.name, {}).get("op_name", ""))]
    around = named + ([o for o in ops if stages.top_level(ctx, o.name)]
                      if edge else [])

    def inside(o, p):
        return (p is not o and p.start <= o.start and o.end <= p.end
                and (p.start, -p.end) < (o.start, -o.end))

    return sum(o.end - o.start for o in named
               if not any(inside(o, p) for p in around)
               and not (edge and stages.top_level(ctx, o.name))) * 1e3


@pytest.mark.parametrize("fixture", sorted(FIXTURES))
@pytest.mark.parametrize("scope", ["checkpoint", "transpose(jvp())",
                                   "rematted_computation", "fwd_bwd"])
def test_recorded_scope_counts_each_event_once(fixture, scope):
    ctx = _recorded(FIXTURES[fixture])
    value = stages.scope_ms(ctx, scope)
    assert value == pytest.approx(_outermost_ms(ctx, scope), rel=1e-9)
    assert 0 < value
    if scope != "fwd_bwd":
        assert value <= _read(ctx, "fwd_bwd_ms")


@pytest.mark.parametrize("fixture", sorted(FIXTURES))
def test_recorded_scope_of_a_stage_reads_the_stage(fixture):
    """The scope of a whole stage reads the stage's top-level time, and
    more only by the loop bodies whose loop began before the recorded step:
    the fixture holds their events, not the loop's. A traced window holds
    the loop's event too, and counts neither."""
    ctx = _recorded(FIXTURES[fixture])
    for metric, stage in (("fwd_bwd_ms", "fwd_bwd"), ("wire_ms", "wire")):
        edge = _outermost_ms(ctx, stage, edge=True)
        assert stages.scope_ms(ctx, stage) == pytest.approx(
            _read(ctx, metric) + edge, rel=1e-9)
        assert edge == 0 or stage == "fwd_bwd"


def test_recorded_dp4_collectives_are_in_their_stages():
    """On four chips the integer all-reduce runs on the XLA Ops line inside
    ``wire``, and ZeRO-1's all-gathers inside ``update``."""
    ctx = _recorded(FIXTURES["granite_dp4"])
    instrs = ctx["instrs"]

    def ms_of(opcode, stage):
        return sum(o.end - o.start for o in ctx["trace"].devices[0]
                   if instrs.get(o.name, {}).get("opcode") == opcode
                   and stages.top_level(ctx, o.name)
                   and stages.stage_of(ctx, o.name) == stage) * 1e3

    all_reduce, all_gather = ms_of("all-reduce", "wire"), ms_of("all-gather", "update")
    assert 5 < all_reduce < _read(ctx, "wire_ms")
    assert 5 < all_gather < _read(ctx, "update_ms")
