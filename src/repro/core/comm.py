"""Communication context: one abstraction for real meshes and simulated workers.

Compressors and aggregators are written against ``CommCtx`` only. The same
code path then runs:

  * inside ``shard_map`` over the production mesh (axes = ("pod","data") or
    ("data",)) — collectives lower to real ICI all-reduce / all-gather;
  * inside ``vmap(axis_name="workers")`` — the n-worker simulation
    used by CPU convergence tests and the paper-reproduction benchmarks.

This is what lets us validate the *distributed algorithm* bit-exactly on a
single CPU device and then lower the identical code for 512 chips. All raw
collectives come from :mod:`repro.parallel.collectives`, the version-portable
layer both execution modes share.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax

from repro.launch import stages
from repro.parallel import collectives as coll
from repro.wire import bucketing


@dataclasses.dataclass(frozen=True)
class CommCtx:
    axes: Tuple[str, ...]  # mesh/vmap axis names holding the data-parallel workers
    axis_sizes: Tuple[int, ...]
    model_axis: str | None = None  # TP axis (for global profiling reductions)
    # overlapped-wire configuration (PR 3): "off" = one monolithic psum of
    # the whole transport tree (the serial reference); "ring" = fixed-size
    # word buckets, each an independent ppermute ring reduce-scatter +
    # all-gather, so XLA can hide bucket k's wire time behind pending
    # compute. Bit-identical decode either way (integer sums are exact).
    overlap: str = "off"
    bucket_words: int = bucketing.DEFAULT_BUCKET_WORDS

    def __post_init__(self):
        if self.overlap not in ("off", "ring"):
            raise ValueError(
                f"unknown overlap mode {self.overlap!r}; options ('off', 'ring')"
            )

    @property
    def n(self) -> int:
        out = 1
        for s in self.axis_sizes:
            out *= s
        return out

    def psum(self, x):
        return coll.psum_tree(x, self.axes)

    def psum_wire(self, ints, wf):
        """Codec-aware integer aggregation: pack each leaf with the wire
        format `wf` into its transport payload (≥1 integer planes), move the
        payload across the data-parallel axes with the collective shape the
        codec declares (the ONLY thing that crosses the wire), and unpack
        back to the summed integer image. Returns ``(words_sum, int_sum)``
        — the fused update route consumes the words directly, everything
        else the image.

        ``wf.transport == "psum"`` (dense/packed) sums the word plane on the
        wire. With ``overlap="ring"`` the words are cut into fixed-size
        buckets (repro.wire.bucketing) and each bucket ring-reduced
        independently; the debucketized word sums are bit-identical to the
        serial psum's, so everything downstream (decode, fused kernels,
        parity tests) is agnostic to which transport ran.

        ``wf.transport == "gather"`` (sparse codecs) all-gathers the payload
        instead — a value is only meaningful next to its index plane, so no
        sum is legal on the wire — and unpack performs the sum by
        scatter-add. The gather route always rides the bucketed layout (one
        bucket when overlap is off, ``bucket_words``-sized buckets under
        "ring" so the gathers interleave with pending compute); the returned
        ``words_sum`` holds the gathered planes with a leading worker axis.
        """
        if getattr(wf, "transport", "psum") == "gather":
            return self._gather_wire(ints, wf)
        return self._psum_wire(ints, wf)

    @stages.scoped("wire")
    def _psum_wire(self, ints, wf):
        """The psum-shaped transport (see :meth:`psum_wire`)."""
        words = jax.tree.map(
            lambda v: wf.pack(v, n_workers=self.n), ints
        )
        if self.overlap == "ring":
            manifest = bucketing.plan_buckets(
                words, bucket_words=self.bucket_words
            )
            buckets = bucketing.bucketize(words, manifest)
            buckets_sum = coll.psum_wire_words_bucketed(
                buckets, self.axes, self.axis_sizes
            )
            words_sum = bucketing.debucketize(buckets_sum, manifest)
        else:
            words_sum = coll.psum_wire_words(words, self.axes)
        int_sum = jax.tree.map(
            lambda w, v: wf.unpack(w, v.shape, n_summed=self.n),
            words_sum,
            ints,
        )
        return words_sum, int_sum

    @stages.scoped("wire")
    def _gather_wire(self, ints, wf):
        """The gather-shaped transport (see :meth:`psum_wire`)."""
        payload = jax.tree.map(
            lambda v: wf.pack(v, n_workers=self.n), ints
        )
        total = sum(l.size for l in jax.tree.leaves(payload))
        bucket_words = (
            self.bucket_words if self.overlap == "ring" else max(total, 1)
        )
        manifest = bucketing.plan_buckets(payload, bucket_words=bucket_words)
        buckets = bucketing.bucketize(payload, manifest)
        gathered_buckets = coll.allgather_wire_words(
            buckets, self.axes, self.axis_sizes
        )
        gathered = bucketing.debucketize_gathered(gathered_buckets, manifest)
        int_sum = jax.tree.map(
            lambda v, p: wf.unpack(p, v.shape, n_summed=self.n),
            ints,
            gathered,
        )
        return gathered, int_sum

    def pmax(self, x):
        return coll.pmax_tree(x, self.axes)

    def pmax_global(self, x):
        """Max over workers AND TP shards (profiling reductions that must see
        the entire model, e.g. Heuristic IntSGD's max_exp). When tp==1 the
        layout folds the model axis into the data-parallel axes (remap_tp1),
        so only append it when it is not already a worker axis."""
        extra = (
            (self.model_axis,)
            if self.model_axis and self.model_axis not in self.axes
            else ()
        )
        return coll.pmax_tree(x, self.axes + extra)

    def pmean(self, x):
        return coll.pmean_tree(x, self.axes, self.n)

    def all_gather(self, x):
        """Gather with a flat leading worker axis of size n."""
        return jax.tree.map(
            lambda v: coll.all_gather_flat(v, self.axes, self.n), x
        )

    def worker_index(self):
        """Linearized data-parallel worker id in [0, n)."""
        return coll.linear_axis_index(self.axes, self.axis_sizes)


def fold_worker_key(key: jax.Array, ctx: CommCtx) -> jax.Array:
    """Independent rounding randomness per worker (required for the 1/n
    variance averaging in Lemma 2's proof — quantization errors must be
    independent across workers)."""
    return jax.random.fold_in(key, ctx.worker_index())
