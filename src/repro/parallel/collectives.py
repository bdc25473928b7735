"""Collectives layer — THE distributed execution surface.

Every shard_map entry point, mesh helper and raw collective the codebase
uses routes through this module, so JAX API drift is absorbed in exactly one
place. It is written for the installed JAX (0.9.0, pinned in
pyproject.toml): ``jax.shard_map`` with its ``check_vma`` flag.

Contract (relied on by launch/step.py, the tests and future backends):

  * :func:`shard_map` — keyword-only (mesh, in_specs, out_specs, check_vma).
  * :func:`sharded_jit` — the step-builder pipeline terminal: shard_map the
    body, jit it with NamedShardings derived from the same specs, optionally
    donate buffers. All step builders terminate here.
  * axis primitives (:func:`psum_tree`, :func:`pmax_tree`, ...) work both
    inside shard_map over a real mesh AND inside ``vmap(axis_name=...)`` —
    which is what lets the n-worker simulation (core/simulate.py) execute
    the identical algorithm on one device.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# shard_map (the ONE place in src/ that touches the raw API)
# ---------------------------------------------------------------------------
def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


# ---------------------------------------------------------------------------
# typed mesh helpers
# ---------------------------------------------------------------------------
def mesh_from_counts(*, data: int = 1, model: int = 1, pod: int | None = None):
    """Build the production-shaped mesh from axis sizes."""
    if pod is not None:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"))
    return jax.make_mesh((data, model), ("data", "model"))


def dp_axes_of(mesh) -> Tuple[str, ...]:
    """The data-parallel (gradient-sync) axes: everything except `model`."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_sizes_of(mesh) -> Tuple[int, ...]:
    return tuple(mesh.shape[a] for a in dp_axes_of(mesh))


def axis_spec(axes: Sequence[str]):
    """PartitionSpec entry for one array dim sharded over `axes`."""
    axes = tuple(axes)
    return axes if len(axes) > 1 else axes[0]


def named_shardings(mesh, tree_specs):
    """PartitionSpec tree -> NamedSharding tree over `mesh`."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def sharded_jit(
    body,
    mesh,
    in_specs,
    out_specs,
    *,
    donate: Tuple[int, ...] = (),
    shard_outputs: bool = True,
    check_vma: bool = False,
):
    """The unified step-builder pipeline terminal: shard_map + jit.

    Returns the jitted function; in/out NamedShardings are derivable from the
    same specs via :func:`named_shardings` (step builders record them on
    their StepArtifacts).
    """
    sm = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
    return jax.jit(
        sm,
        in_shardings=named_shardings(mesh, in_specs),
        out_shardings=named_shardings(mesh, out_specs) if shard_outputs else None,
        donate_argnums=donate,
    )


# ---------------------------------------------------------------------------
# axis primitives (work under shard_map AND vmap(axis_name=...))
# ---------------------------------------------------------------------------
def axis_index(axis: str):
    return lax.axis_index(axis)


def linear_axis_index(axes: Sequence[str], sizes: Sequence[int]):
    """Row-major linearized index over several mesh axes, in [0, prod(sizes))."""
    idx = 0
    for ax, size in zip(axes, sizes):
        idx = idx * size + lax.axis_index(ax)
    return idx


def psum(v, axes):
    """Single-array float/int psum. The sanctioned spelling of ``lax.psum``
    everywhere outside this module (linter rule C001): model-axis activation
    reductions and the scalar loss/metric reductions the wire auditor's
    W001 allowance covers. Gradient-sized dp-axis payloads do NOT belong
    here — they ride :func:`psum_wire_words` as integers."""
    return lax.psum(v, axes)


def pmax(v, axes):
    """Single-array pmax (see :func:`psum` for the C001 contract)."""
    return lax.pmax(v, axes)


def all_to_all(v, axis: str, *, split_axis: int, concat_axis: int,
               tiled: bool = False):
    """Portable ``lax.all_to_all`` (MoE expert-parallel shuffles)."""
    return lax.all_to_all(
        v, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def psum_tree(x, axes):
    return jax.tree.map(lambda v: lax.psum(v, axes), x)


def _check_wire_dtypes(words):
    for v in jax.tree.leaves(words):
        if not jnp.issubdtype(v.dtype, jnp.integer):
            raise TypeError(
                f"wire payload must be integer, got {v.dtype} — the IntSGD "
                "wire carries no floats (route float reductions through "
                "psum_tree instead)"
            )


def psum_wire_words(words, axes):
    """The packed-word integer all-reduce — THE floatless-wire primitive.

    Every wire-codec transport (dense lanes, bit-packed int32 words) sums
    its payload through here; the dtype guard makes the paper's no-floats
    contract structural: a float leaf on the gradient wire is a bug, not a
    silent fallback. Wrap-around integer addition is exactly what the
    packed-field arithmetic needs (see repro/wire/packed.py).

    The whole tree rides ONE psum primitive — the serial reference the
    bucketed route (:func:`psum_wire_words_bucketed`) is measured against:
    one monolithic collective on the critical path vs many interleavable
    ones (benchmarks/bench_overlap.py counts exactly this). ``lax.psum`` of
    a tree binds one psum per leaf, so the leaves are concatenated into one
    flat payload first (one per word dtype; a codec has one) and split back
    after the sum, as the bucketed route does.
    """
    _check_wire_dtypes(words)
    leaves, treedef = jax.tree.flatten(words)
    out = list(leaves)
    by_dtype = {}
    for i, v in enumerate(leaves):
        by_dtype.setdefault(v.dtype, []).append(i)
    for idx in by_dtype.values():
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in idx])
        summed = lax.psum(flat, axes)
        off = 0
        for i in idx:
            v = leaves[i]
            out[i] = summed[off: off + v.size].reshape(v.shape)
            off += v.size
    return jax.tree.unflatten(treedef, out)


def ring_allreduce_int(v, axis: str, n: int):
    """Integer all-reduce of one flat array as a ``lax.ppermute`` ring
    reduce-scatter followed by an all-gather (the SwitchML/NCCL shape).

    Why not one psum: a psum is a single opaque collective on the critical
    path. The ring decomposes it into n-1 chunk-sized ppermute hops plus a
    chunk all-gather — independent ops XLA's latency-hiding scheduler can
    overlap with pending compute (the next bucket's pack, the next
    microbatch's backward). Integer addition is exact and associative
    (wrap-around mod 2^width), so the ring sum is BIT-IDENTICAL to the psum
    for any hop order — dense lanes never overflow mid-ring (any partial sum
    of k <= n §5.1-clipped values fits the lane), packed words wrap exactly
    per field. Works under shard_map AND vmap(axis_name), like every other
    primitive here.
    """
    if n <= 1:
        return v
    size = v.size
    c = -(-size // n)  # ring chunk: pad only to a multiple of n
    chunks = jnp.pad(v.reshape(-1), (0, n * c - size)).reshape(n, c)
    i = lax.axis_index(axis)
    perm = [(d, (d + 1) % n) for d in range(n)]
    take = lambda j: lax.dynamic_index_in_dim(
        chunks, jnp.mod(j, n), 0, keepdims=False
    )
    # reduce-scatter: after step s the in-flight partial for chunk
    # (i - s - 2) mod n has accumulated s + 2 contributions; after n-1 steps
    # device i holds the full sum of chunk i.
    send = take(i - 1)
    for s in range(n - 1):
        recvd = lax.ppermute(send, axis, perm)
        send = recvd + take(i - s - 2)
    # all-gather of the finished chunks (device i contributed chunk i, so
    # the gathered rows are already in chunk order)
    out = lax.all_gather(send, axis)
    return out.reshape(-1)[:size].reshape(v.shape)


def psum_wire_words_bucketed(buckets, axes, sizes):
    """Bucketed async-capable integer all-reduce — the ``overlap`` wire.

    ``buckets`` is the list of fixed-size 1-D word buckets cut by
    :mod:`repro.wire.bucketing`; each is ring-reduced independently
    (sequentially over multi-axis dp grids: a ring per mesh axis), emitting
    2+ small collectives per bucket instead of one monolithic psum, so the
    XLA scheduler can double-buffer bucket k's wire time against whatever
    compute is still pending. Bit-identical to ``psum_wire_words`` on the
    debucketized tree (integer addition is exact in any order); same dtype
    guard — the floatless wire stays structural on the overlapped route.
    """
    _check_wire_dtypes(buckets)

    def _one(b):
        for ax, n in zip(axes, sizes):
            b = ring_allreduce_int(b, ax, n)
        return b

    return [_one(b) for b in buckets]


def allgather_wire_words(payload, axes, sizes):
    """Integer all-gather of a transport payload tree — the gather-shaped
    wire primitive (sparse codecs: value + index planes that must arrive
    intact because no cross-worker sum is meaningful on the wire).

    Same structural floatless-wire guard as :func:`psum_wire_words`; every
    plane comes back with a flat leading worker axis of size prod(sizes),
    ordered to match :func:`linear_axis_index` (row-major over `axes`, the
    same order :func:`all_gather_flat` uses). A size-1 axis short-circuits
    in Python and emits nothing, mirroring :func:`ring_allreduce_int` — the
    static transport model (`traffic.plan_transport`, gather branch) counts
    exactly the eqns emitted here.
    """
    _check_wire_dtypes(payload)
    pairs = tuple((ax, s) for ax, s in zip(axes, sizes))
    n = 1
    for _, s in pairs:
        n *= s

    def _one(v):
        out = v
        for ax, s in reversed(pairs):
            if s > 1:
                out = lax.all_gather(out, ax)
        return out.reshape((n,) + v.shape)

    return jax.tree.map(_one, payload)


def pmax_tree(x, axes):
    return jax.tree.map(lambda v: lax.pmax(v, axes), x)


def pmean_tree(x, axes, n: int):
    return jax.tree.map(lambda v: lax.psum(v, axes) / n, x)


def all_gather_flat(v, axes: Sequence[str], n: int):
    """Gather one array over `axes` with a flat leading worker axis of size n.

    Worker order matches :func:`linear_axis_index` (row-major over `axes`).
    """
    out = v
    for ax in reversed(tuple(axes)):
        out = lax.all_gather(out, ax)
    return out.reshape((n,) + v.shape)


def all_gather_concat(v, axes: Sequence[str]):
    """Gather over `axes` concatenating along the existing leading dim
    (the ZeRO-1 param all-gather layout), worker order as
    :func:`linear_axis_index`. Tiled gathers never stack an (n, ...)
    intermediate, which the TPU compiler takes minutes over for a large
    leaf."""
    out = v
    for ax in reversed(tuple(axes)):
        out = lax.all_gather(out, ax, tiled=True)
    return out


def ppermute_ring(x, axis: str, n: int, *, shift: int = 1):
    """Send to the next device on a ring over `axis` (pipeline transfers)."""
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


# ---------------------------------------------------------------------------
# single-device simulation of the worker axis
# ---------------------------------------------------------------------------
WORKER_AXIS = "workers"


def vmap_workers(fn, in_axes, *, axis: str = WORKER_AXIS):
    """vmap with an axis name: the n-worker simulation entry point. The axis
    primitives above lower identically under this and under shard_map, which
    is what lets CPU convergence tests validate the distributed algorithm."""
    return jax.vmap(fn, in_axes=in_axes, axis_name=axis)
