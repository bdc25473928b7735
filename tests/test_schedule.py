"""PR 9: the static performance auditor — schedule (P) + traffic (T) layers.

Four groups:

  * PLANTED REGRESSIONS, one per P-rule: serialize a bucket consumer into
    the next image's backward (P001), duplicate / kill / cast-round-trip a
    wire collective (P002), widen a fused kernel operand past the codec's
    per-image byte budget (P003) — each must be flagged BY RULE ID, and the
    un-planted twin must stay clean;
  * the BYTE ACCOUNTANT's equality contract: the static transport model
    (``repro.analysis.traffic``) meters exactly what the ``Logged`` codec
    meters and what ``BucketManifest`` records, across every codec × worker
    count × microbatch count (hypothesis property + deterministic pins);
  * T-rule drift: an eqn-level transport that disagrees with the declared
    model (wrong bytes, wrong collective count) is named T001/T002;
  * the COMPOSED audit (`full_audit` / `verify_step`): suppression spans
    W/P/T, and the real 4-device trace passes all three layers with the
    roofline the overlap design promises.
"""
import os
import textwrap

import pytest

import jax
import jax.numpy as jnp
from jax import lax

from conftest import REPO, run_forced_mesh as _run

from repro.analysis import jaxpr_walk as jw
from repro.analysis import schedule as sched
from repro.analysis import traffic as tr
from repro.analysis import wire_audit as wa
from repro.parallel import collectives as coll
from repro.wire import Logged, WireRangeError, make_wire_format, plan_buckets

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# toy tracing helpers (the test_analysis.py pattern: 1-device mesh keeps the
# collective eqns in the jaxpr; the SPEC declares what is proven)
# ---------------------------------------------------------------------------
def _toy_jaxpr(body, *structs):
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    sm = coll.shard_map(
        body, mesh=mesh, in_specs=(P(),) * len(structs), out_specs=P()
    )
    return jax.make_jaxpr(sm)(*structs)


def _spec(**kw):
    base = dict(
        dp_axes=("data",), axis_sizes={"data": 4}, n_workers=4,
        wire_kind="dense", bits=8,
    )
    base.update(kw)
    return wa.WireSpec(**base)


F32 = jax.ShapeDtypeStruct((256,), jnp.float32)


def _ints(x):
    return jnp.clip(jnp.round(x), -3, 3).astype(jnp.int32)


def _rules(report):
    return sorted({v.rule for v in report.violations})


# ---------------------------------------------------------------------------
# P001: a reduce's result feeding compute a later reduce depends on
# ---------------------------------------------------------------------------
def test_p001_decoded_sum_feeds_next_images_backward():
    def step(x, w):
        # image 0's reduce ...
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        s0 = lax.psum(_ints(x), "data")
        # ... DECODED INTO image 1's matmul: the planted pipeline break
        y = jnp.dot(s0.astype(jnp.float32).reshape(16, 16), w)
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        s1 = lax.psum(_ints(y.reshape(-1)), "data")
        return s0.sum() + s1.sum()

    closed = _toy_jaxpr(step, F32, jax.ShapeDtypeStruct((16, 16), jnp.float32))
    rep = sched.analyze_schedule(closed, _spec())
    assert any(v.rule == "P001" for v in rep.violations), _rules(rep)
    assert "pipelining is" in " ".join(
        v.message for v in rep.violations if v.rule == "P001"
    )


def test_p001_independent_images_clean():
    def step(x, y):
        # two data-independent images: reduces may land in either order
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        s0 = lax.psum(_ints(x), "data")
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        s1 = lax.psum(_ints(y), "data")
        return s0.sum() + s1.sum()

    closed = _toy_jaxpr(step, F32, F32)
    rep = sched.analyze_schedule(closed, _spec())
    assert not any(v.rule == "P001" for v in rep.violations), _rules(rep)
    # and the roofline sees the wire-wire concurrency
    assert rep.n_wire_collectives == 2
    assert rep.n_serialized == 0
    assert rep.interleavable_fraction == 1.0


# ---------------------------------------------------------------------------
# P002: dead / duplicate collectives, cast round-trips
# ---------------------------------------------------------------------------
def test_p002_duplicate_psum_flagged():
    def step(x):
        ints = _ints(x)
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        a = lax.psum(ints, "data")
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        b = lax.psum(ints, "data")  # the same sum, twice on the wire
        return a + b

    rep = sched.analyze_schedule(_toy_jaxpr(step, F32), _spec())
    dups = [v for v in rep.violations
            if v.rule == "P002" and "duplicate" in v.message]
    assert len(dups) == 1, _rules(rep)


def test_p002_dead_collective_flagged():
    def step(x):
        ints = _ints(x)
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        dead = lax.psum(ints, "data")  # never reaches the outputs
        del dead
        return ints.sum()

    rep = sched.analyze_schedule(_toy_jaxpr(step, F32), _spec())
    assert any(
        v.rule == "P002" and "dead" in v.message for v in rep.violations
    ), _rules(rep)


def test_p002_int_cast_roundtrip_flagged():
    def step(x):
        ints = _ints(x)
        narrowed = ints.astype(jnp.int16).astype(jnp.int32)  # the round-trip
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        return lax.psum(narrowed, "data")

    rep = sched.analyze_schedule(_toy_jaxpr(step, F32), _spec())
    trips = [v for v in rep.violations
             if v.rule == "P002" and "round-trip" in v.message]
    assert trips, _rules(rep)
    assert "int16" in trips[0].where


def test_p002_float_mixed_precision_chain_not_flagged():
    # f32 -> bf16 compute -> f32 grads is the mixed-precision recipe, not
    # wasted wire work: the round-trip rule is integer-only
    def step(x):
        h = x.astype(jnp.bfloat16)
        g = (h * 2).astype(jnp.float32)
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        return lax.psum(_ints(g), "data")

    rep = sched.analyze_schedule(_toy_jaxpr(step, F32), _spec())
    assert not any(v.rule == "P002" for v in rep.violations), _rules(rep)


# ---------------------------------------------------------------------------
# P003: fused-route per-eqn HBM byte budget (both codecs)
# ---------------------------------------------------------------------------
def _fused_spec(**kw):
    return _spec(
        wire_kind="packed", bits=8, use_kernels=True, fused=True, **kw
    )


def test_p003_widened_fused_operand_flagged():
    kops = pytest.importorskip("repro.kernels.ops")

    def step(image, param, mom):
        scal = jnp.ones((5,), jnp.float32)
        p, (m,), _ = kops.fused_apply(
            image, param, (mom,), scal, kernel="sgd", interpret=True
        )
        return p + 0.0 * m

    structs = (
        jax.ShapeDtypeStruct((1024,), jnp.int32),  # 4096 B for a 1024 B budget
        jax.ShapeDtypeStruct((1024,), jnp.float32),
        jax.ShapeDtypeStruct((1024,), jnp.float32),
    )
    rep = sched.analyze_schedule(
        jax.make_jaxpr(step)(*structs), _fused_spec()
    )
    p3 = [v for v in rep.violations if v.rule == "P003"]
    assert p3, _rules(rep)
    assert "budget" in p3[0].message


def test_p003_packed_words_within_budget_clean():
    kops = pytest.importorskip("repro.kernels.ops")

    def step(words, param, mom):
        scal = jnp.ones((5,), jnp.float32)
        p, (m,), _ = kops.fused_unpack_apply(
            words, param, (mom,), scal, None,
            kernel="sgd", bits=8, n_summed=4, interpret=True,
        )
        return p + 0.0 * m

    structs = (
        jax.ShapeDtypeStruct((256,), jnp.int32),  # 1024 B == the budget
        jax.ShapeDtypeStruct((1024,), jnp.float32),
        jax.ShapeDtypeStruct((1024,), jnp.float32),
    )
    rep = sched.analyze_schedule(
        jax.make_jaxpr(step)(*structs), _fused_spec()
    )
    assert not any(v.rule == "P003" for v in rep.violations), _rules(rep)


# ---------------------------------------------------------------------------
# schedule classification: serialized vs eligible
# ---------------------------------------------------------------------------
def test_monolithic_psum_is_serialized():
    def step(x):
        # every value feeds the reduce, nothing is concurrent with it
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        return lax.psum(_ints(x), "data")

    rep = sched.analyze_schedule(_toy_jaxpr(step, F32), _spec())
    assert rep.n_wire_collectives == 1
    assert rep.n_serialized == 1
    assert rep.hidden_fraction == 0.0
    assert rep.interleavable_fraction == 0.0


def test_concurrent_dot_makes_collective_hideable():
    def step(x, a, b):
        # the matmul neither feeds nor consumes the reduce: hideable work
        y = jnp.dot(a, b)
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        s = lax.psum(_ints(x), "data")
        return s.sum() + y.sum()

    m = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    rep = sched.analyze_schedule(_toy_jaxpr(step, F32, m, m), _spec())
    assert rep.n_wire_collectives == 1
    assert rep.n_serialized == 0
    assert rep.hidden_fraction == 1.0
    row = rep.collectives[0]
    assert row["eligible"] and row["concurrent_flops"] >= 2 * 32 * 32 * 32


# ---------------------------------------------------------------------------
# the byte accountant == Logged metering == BucketManifest
# ---------------------------------------------------------------------------
ALL_CODECS = ["dense4", "dense8", "dense16", "dense32",
              "packed4", "packed8", "packed16",
              "topk8:32", "topk16:8"]


def _meter_logged(codec, leaf_sizes, n, M):
    """Trace M images' worth of pack calls through a Logged codec and return
    the metered wire bytes (trace only, nothing executed)."""
    logged = Logged(make_wire_format(codec))

    def pack_all():
        return [
            logged.pack(jnp.zeros((s,), jnp.int32), n_workers=n)
            for _ in range(M)
            for s in leaf_sizes
        ]

    jax.eval_shape(pack_all)
    return logged.pack_bytes


def _declared_leaf_bytes(wf, size):
    """The accountant's per-leaf arithmetic for any codec kind."""
    return tr.payload_bytes(wf.name, wf.bits, size, k=getattr(wf, "k", 0))


@pytest.mark.parametrize("codec", ALL_CODECS)
def test_static_payload_equals_logged_metering(codec):
    wf = make_wire_format(codec)
    leaf_sizes, n, M = (129, 64, 7), 4, 2
    declared = sum(_declared_leaf_bytes(wf, s) for s in leaf_sizes) * M
    assert declared == _meter_logged(codec, leaf_sizes, n, M)
    # and the per-leaf arithmetic IS the codec's own wire_bytes
    for s in leaf_sizes:
        assert _declared_leaf_bytes(wf, s) == wf.wire_bytes(s)


if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        codec=st.sampled_from(ALL_CODECS),
        leaf_sizes=st.lists(
            st.integers(min_value=1, max_value=300), min_size=1, max_size=4
        ),
        n=st.integers(min_value=1, max_value=8),
        M=st.integers(min_value=1, max_value=4),
    )
    def test_static_accountant_matches_logged_property(
        codec, leaf_sizes, n, M
    ):
        wf = make_wire_format(codec)
        if (2 ** (wf.bits - 1) - 1) // n == 0:
            # a 4-bit wire cannot carry a sum over 8 workers: the codec
            # refuses it by design
            with pytest.raises(WireRangeError):
                _meter_logged(codec, leaf_sizes, n, M)
            return
        declared = sum(_declared_leaf_bytes(wf, s) for s in leaf_sizes) * M
        assert declared == _meter_logged(codec, leaf_sizes, n, M)


def test_plan_bucket_sizes_matches_plan_buckets():
    wf = make_wire_format("packed8")
    leaf_sizes = (5000, 3000, 171)
    words_struct = jax.eval_shape(
        lambda: [
            wf.pack(jnp.zeros((s,), jnp.int32), n_workers=4)
            for s in leaf_sizes
        ]
    )
    manifest = plan_buckets(words_struct, bucket_words=512)
    total_words = sum(
        tr.leaf_wire_words("packed", 8, s) for s in leaf_sizes
    )
    assert manifest.total_words == total_words
    assert manifest.bucket_sizes == tr.plan_bucket_sizes(total_words, 512)
    assert manifest.payload_bytes == sum(
        tr.payload_bytes("packed", 8, s) for s in leaf_sizes
    )


def test_manifest_ring_collectives_matches_transport_plan():
    wf = make_wire_format("packed8")
    leaf_sizes, n, M, B = (5000, 3000, 171), 4, 2, 512
    words_struct = jax.eval_shape(
        lambda: [
            wf.pack(jnp.zeros((s,), jnp.int32), n_workers=n)
            for s in leaf_sizes
        ]
    )
    manifest = plan_buckets(words_struct, bucket_words=B)
    spec = _spec(
        axis_sizes={"data": n}, n_workers=n, n_accum=M,
        wire_kind="packed", bits=8, leaf_sizes=leaf_sizes,
        overlap="ring", bucket_words=B,
    )
    plan = tr.plan_transport(spec)
    ring_eqns, ring_bytes = manifest.ring_collectives((n,))
    assert ring_eqns * M == plan.n_eqns
    assert ring_bytes * M == plan.coll_bytes
    # a size-1 axis short-circuits: no collectives at all
    assert manifest.ring_collectives((1,)) == (0, 0)


# ---------------------------------------------------------------------------
# T-rules: eqn-level drift from the declared transport
# ---------------------------------------------------------------------------
def test_traffic_serial_route_clean():
    def step(x):
        # dense8 transport: one int8 psum carrying exactly size bytes
        ints = jnp.clip(jnp.round(x), -3, 3).astype(jnp.int8)
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        return lax.psum(ints, "data")

    spec = _spec(leaf_sizes=(256,), overlap="off")
    rep = tr.account_traffic(_toy_jaxpr(step, F32), spec)
    assert rep.ok, _rules(rep)
    assert rep.observed_eqns == rep.plan.n_eqns == 1
    assert rep.observed_bytes == rep.plan.coll_bytes == 256


def test_t001_widened_wire_flagged():
    def step(x):
        ints = _ints(x)  # int32 on the wire: 4x the declared dense8 payload
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        return lax.psum(ints, "data")

    spec = _spec(leaf_sizes=(256,), overlap="off")
    rep = tr.account_traffic(_toy_jaxpr(step, F32), spec)
    assert _rules(rep) == ["T001"]
    assert "1024 != declared transport 256" in rep.violations[0].message


def test_t002_split_collective_flagged():
    def step(x):
        ints = jnp.clip(jnp.round(x), -3, 3).astype(jnp.int8)
        # same payload, two eqns: count drift without byte drift
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        a = lax.psum(ints[:128], "data")
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        b = lax.psum(ints[128:], "data")
        return jnp.concatenate([a, b])

    spec = _spec(leaf_sizes=(256,), overlap="off")
    rep = tr.account_traffic(_toy_jaxpr(step, F32), spec)
    assert _rules(rep) == ["T002"]


def test_traffic_skipped_without_leaf_sizes():
    def step(x):
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        return lax.psum(_ints(x), "data")

    rep = tr.account_traffic(_toy_jaxpr(step, F32), _spec())
    assert rep.plan is None and rep.ok  # hand-built spec: nothing declared


# ---------------------------------------------------------------------------
# the composed audit: suppression + report shape
# ---------------------------------------------------------------------------
def test_full_audit_suppression_spans_rule_families():
    def step(x):
        ints = _ints(x)
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        a = lax.psum(ints, "data")
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        b = lax.psum(ints, "data")  # planted P002 duplicate
        return a + b

    closed = _toy_jaxpr(step, F32)
    spec = _spec(bits=32, wire_kind="dense")
    rep = sched.full_audit(closed, spec)
    assert any(v.rule == "P002" for v in rep.violations)
    waived = sched.full_audit(
        closed, spec, suppress={"P002": "planted fixture for this test"}
    )
    assert not any(v.rule == "P002" for v in waived.violations)
    assert any(v.rule == "P002" for v, _why in waived.suppressed)
    with pytest.raises(ValueError, match="unknown rule"):
        sched.full_audit(closed, spec, suppress={"Z999": "nope"})
    with pytest.raises(ValueError, match="justification"):
        sched.full_audit(closed, spec, suppress={"P002": "  "})


def test_full_report_dict_has_all_sections():
    def step(x):
        # lint: allow(C001) -- audit fixture: the raw collective IS the subject under test
        return lax.psum(_ints(x), "data")

    d = sched.full_audit(_toy_jaxpr(step, F32), _spec(bits=32)).to_dict()
    assert "schedule" in d and "traffic" in d
    assert {"hidden_fraction", "interleavable_fraction", "collectives"} \
        <= set(d["schedule"])
    assert {"declared", "observed_eqns", "observed_bytes"} \
        <= set(d["traffic"])


def test_matrix_diff_ignores_timing_and_names_drift():
    from repro.analysis.__main__ import _diff_reports

    base = {
        "points": [
            {"config": "a", "codec": "packed8", "overlap": "off",
             "microbatches": 1, "fused": False, "ok": True,
             "violations": [], "seconds": 1.0},
            {"config": "a", "codec": "packed8", "overlap": "ring",
             "microbatches": 4, "fused": False, "ok": True,
             "violations": [], "seconds": 2.0},
        ],
        "lint": [],
    }
    import copy

    same = copy.deepcopy(base)
    same["points"][0]["seconds"] = 99.0  # timings churn freely
    assert _diff_reports(base, same) == []

    removed = copy.deepcopy(base)
    removed["points"].pop()
    drift = _diff_reports(base, removed)
    assert len(drift) == 1 and "removed" in drift[0]

    flipped = copy.deepcopy(base)
    flipped["points"][1]["ok"] = False
    flipped["points"][1]["violations"] = [
        {"rule": "T001", "where": "w", "message": "m"}
    ]
    drift = _diff_reports(base, flipped)
    assert len(drift) == 1
    assert "verdict changed" in drift[0] and "T001" in drift[0]


def test_rule_ids_disjoint_across_families():
    fams = [wa.RULES, sched.RULES, tr.RULES]
    ids = [r for fam in fams for r in fam]
    assert len(ids) == len(set(ids))
    assert {r[0] for r in ids} == {"W", "P", "T"}


# ---------------------------------------------------------------------------
# the real thing: 4-device forced-mesh trace through all three layers
# ---------------------------------------------------------------------------
def test_forced_mesh_full_audit_and_roofline():
    """ring × M=2 on 4 workers: W/P/T all clean, byte/count equality exact,
    and the static roofline certifies the pipelined wire as interleavable —
    while the serial M=1 psum stays serialized. Also exercises
    build_train_step(verify='static') end to end."""
    _run(
        textwrap.dedent(
            """
            import jax
            from repro.analysis import schedule as sched
            from repro.configs import ShapeConfig, get_arch, smoke_config
            from repro.core import make_compressor
            from repro.launch.step import build_train_step
            from repro.optim import sgd
            from repro.optim.schedules import constant

            mesh = jax.make_mesh((4, 1), ("data", "model"))

            def build(**kw):
                return build_train_step(
                    smoke_config(get_arch("xlstm-125m")), mesh,
                    ShapeConfig("t", 32, 8, "train"),
                    compressor=make_compressor(
                        "intsgd", bits=8, wire="packed8"
                    ),
                    base_opt=sgd(momentum=0.9), lr_schedule=constant(0.1),
                    tp_override=1, **kw,
                )

            # pipelined ring: verify='static' runs the full W/P/T audit
            art = build(overlap="ring", microbatches=2, verify="static")
            rep = sched.verify_step(art)
            assert rep.ok, rep.violations
            s, t = rep.schedule, rep.traffic
            assert t.plan is not None
            assert t.observed_bytes == t.plan.coll_bytes
            assert t.observed_eqns == t.plan.n_eqns
            assert s.interleavable_fraction == 1.0, s.to_dict()
            assert s.hidden_fraction == 1.0, s.to_dict()

            # monolithic serial psum: structurally serialized
            rep1 = sched.verify_step(build(overlap="off", microbatches=1))
            assert rep1.ok, rep1.violations
            assert rep1.schedule.n_wire_collectives == 1
            assert rep1.schedule.n_serialized == 1
            assert rep1.traffic.observed_bytes == rep1.traffic.plan.coll_bytes
            print("full audit ok")
            """
        )
    )
