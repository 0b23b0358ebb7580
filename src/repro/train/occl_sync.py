"""OCCL-based gradient synchronization (the paper's DNN-training use).

Gradients are flattened into size-bounded BUCKETS (paper Sec. 5.3.1: 161
all-reduces for ResNet50, one per parameter tensor group).  Each bucket is
registered once as an OCCL all-reduce on the DP communicator; every step
the ranks submit their buckets **in backward order with rising priority**
(the Priority-based Ordering policy of Sec. 3.2 — later gradients are
needed first by the optimizer of the next layer-ordered pass, so they
overlap with remaining backward compute), and the daemon gang-schedules
them decentrally.

Ranks here are the simulated DP workers of the sim backend (one device,
vmapped) — the same scheduler core drives the shard_map mesh backend on a
real fleet.  The "static" comparator (statically-sequenced NCCL of the
paper's Sec. 5) is plain jnp summation in a fixed bucket order.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (CollKind, OcclConfig, OcclRuntime, OrderPolicy,
                    registered_heap_elems)
from ..core.trace import span


@dataclasses.dataclass
class Bucket:
    coll_id: int
    leaf_ids: list[int]
    sizes: list[int]
    total: int


class OcclGradSync:
    """compress_wire: bf16 gradient payloads on the connector fabric
    (half the wire bytes; accumulation stays f32 on-host via the heap
    dtype) — the gradient-compression option of DESIGN.md §6."""

    def __init__(self, grads_template, n_ranks: int,
                 bucket_elems: int = 4096, slice_elems: int = 256,
                 priority_preempts: bool = False,
                 compress_wire: bool = False,
                 hierarchy: tuple | None = None,
                 burst_slices: int = 1,
                 bandwidth_groups: int = 0,
                 intra_burst_cap: int = 0,
                 inter_burst_cap: int = 0):
        """``hierarchy=(G, N)`` routes every bucket through the composite
        two-level all-reduce (intra-group reduce-scatter -> inter-group
        all-reduce -> intra-group all-gather over the G x N rank grid,
        chained on device) instead of the flat ring — the node-aware
        topology of real fleets, where N is the intra-node (fast-domain)
        size.  Requires G * N == n_ranks.

        ``burst_slices``/``bandwidth_groups``/``intra_burst_cap``/
        ``inter_burst_cap`` forward the bandwidth-skew lane model
        (config.py) into the grad-sync runtime — the setting the overlap
        perf gate measures under (skewed lanes need ``burst_slices > 1``
        for the caps to differentiate intra/inter traffic)."""
        leaves = jax.tree_util.tree_leaves(grads_template)
        self.treedef = jax.tree_util.tree_structure(grads_template)
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.n_ranks = n_ranks

        # --- bucketize leaves in reverse (backward) order ----------------
        buckets: list[Bucket] = []
        cur_ids: list[int] = []
        cur_sizes: list[int] = []
        cur_total = 0
        for i in reversed(range(len(leaves))):
            n = int(np.prod(leaves[i].shape))
            if cur_total + n > bucket_elems and cur_ids:
                buckets.append(Bucket(-1, cur_ids, cur_sizes, cur_total))
                cur_ids, cur_sizes, cur_total = [], [], 0
            cur_ids.append(i)
            cur_sizes.append(n)
            cur_total += n
        if cur_ids:
            buckets.append(Bucket(-1, cur_ids, cur_sizes, cur_total))
        self.buckets = buckets

        self.compress_wire = compress_wire
        self.hierarchy = hierarchy
        if hierarchy is not None:
            G, N = hierarchy
            assert G * N == n_ranks, (
                f"hierarchy {hierarchy} does not tile {n_ranks} ranks")
        # A two-level bucket is a 3-stage chain: 3 collective slots per
        # bucket and two lanes (all buckets share the derived intra and
        # inter partitions; the logical group claims NO lane of its own).
        n_colls = len(buckets) * (3 if hierarchy is not None else 1)
        cfg = OcclConfig(
            n_ranks=n_ranks,
            max_colls=max(8, n_colls),
            max_comms=2 if hierarchy is not None else 1,
            slice_elems=slice_elems,
            conn_depth=max(8, 3 * burst_slices),
            burst_slices=burst_slices,
            # In-step submission appends one SQE per bucket per rank into
            # the device SQ (no host pack_sq between them) — the SQ must
            # hold a whole step's buckets.
            sq_len=max(64, len(buckets) + 4),
            order_policy=OrderPolicy.PRIORITY,
            priority_preempts=priority_preempts,
            superstep_budget=1 << 16,
            dtype="bfloat16" if compress_wire else "float32",
            bandwidth_groups=bandwidth_groups,
            intra_burst_cap=intra_burst_cap,
            inter_burst_cap=inter_burst_cap,
        )
        # Each arena holds exactly what the bucket registrations take from
        # it (padded chunk layouts and two-level intermediates included).
        heap = registered_heap_elems(cfg, self._register)
        self.occl = OcclRuntime(dataclasses.replace(cfg, heap_elems=heap))
        for b, cid in zip(buckets, self._register(self.occl)):
            b.coll_id = cid

    def _register(self, rt: OcclRuntime) -> list:
        """Register every bucket as one all-reduce on ``rt``."""
        R = self.n_ranks
        comm = (rt.communicator(list(range(R))) if self.hierarchy is None
                else rt.logical_communicator(list(range(R))))
        return [rt.register(CollKind.ALL_REDUCE, comm, n_elems=b.total,
                            algo="ring" if self.hierarchy is None
                            else "two_level",
                            hierarchy=self.hierarchy)
                for b in self.buckets]

    # ------------------------------------------------------------------
    def _pack(self, grads, bucket: Bucket) -> np.ndarray:
        with span("pack") as sp:
            leaves = jax.tree_util.tree_leaves(grads)
            parts = [np.asarray(leaves[i], np.float32).ravel()
                     for i in bucket.leaf_ids]
            out = np.concatenate(parts)
            if self.compress_wire:
                out = np.asarray(jnp.asarray(out, jnp.bfloat16))
            sp.set_metadata(bytes=out.nbytes)
        return out

    # -- overlap-mode helpers (train/step.py custom_vjp boundaries) -------
    def device_api(self):
        """The runtime's in-trace submission/tick API (core/device_api.py)
        bound to this sync's bucket registrations."""
        return self.occl.device_api()

    def unflatten(self, flats_by_bucket: Sequence) -> object:
        """Rebuild one rank's gradient pytree from per-bucket flat traced
        arrays (already averaged), in bucket-index order."""
        leaves = [None] * len(self.shapes)
        for b, flat in zip(self.buckets, flats_by_bucket):
            off = 0
            for i, n in zip(b.leaf_ids, b.sizes):
                leaves[i] = flat[off:off + n].reshape(
                    self.shapes[i]).astype(self.dtypes[i])
                off += n
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def all_reduce(self, per_rank_grads: Sequence) -> list:
        """Average gradients across ranks via OCCL collectives.

        per_rank_grads: list of grad pytrees (one per DP rank, any
        submission order is fine — the runtime is deadlock-free)."""
        assert len(per_rank_grads) == self.n_ranks
        for prio, b in enumerate(self.buckets):
            for r in range(self.n_ranks):
                # Payloads are STAGED host-side and flushed to the device
                # in one batched scatter by the first launch prologue —
                # one staging transfer per step (runtime._flush_staged).
                self.occl.submit(r, b.coll_id, prio=prio,
                                 data=self._pack(per_rank_grads[r], b))
        self.occl.drive()
        reads = self.occl.read_outputs_bulk(
            [(r, b.coll_id) for r in range(self.n_ranks)
             for b in self.buckets])

        with span("unpack", bytes=sum(v.nbytes for v in reads.values())):
            outs = []
            for r in range(self.n_ranks):
                leaves = [None] * len(self.shapes)
                for b in self.buckets:
                    # read_outputs_bulk returns owned copies, so the
                    # average can be taken in place without corrupting
                    # sibling reads.
                    flat = np.asarray(reads[(r, b.coll_id)], np.float32)
                    flat /= self.n_ranks
                    off = 0
                    for i, n in zip(b.leaf_ids, b.sizes):
                        leaves[i] = jnp.asarray(
                            flat[off:off + n].reshape(self.shapes[i]),
                            self.dtypes[i])
                        off += n
                outs.append(
                    jax.tree_util.tree_unflatten(self.treedef, leaves))
        return outs

    def evict(self, rank: int) -> dict:
        """Elastically drop one DP worker: delegates to
        ``OcclRuntime.evict`` (drain -> rebuild for R-1 -> replay) and
        shrinks this sync's own rank count.  Bucket registrations survive
        via their :class:`~repro.core.handles.CollectiveHandle`\\ s —
        ``all_reduce`` keeps working unchanged on the smaller fleet, and
        a mid-flight eviction replays the surviving ranks' staged bucket
        payloads.  A two-level hierarchy that no longer tiles the shrunk
        fleet falls back to the auto-derived grid (evict()'s replay
        rule), so ``self.hierarchy`` is cleared when it stops tiling."""
        report = self.occl.evict(rank)
        self.n_ranks = self.occl.cfg.n_ranks
        if self.hierarchy is not None:
            G, N = self.hierarchy
            if G * N != self.n_ranks:
                self.hierarchy = None
        return report

    def stats(self):
        return self.occl.stats()


def static_all_reduce(per_rank_grads: Sequence) -> list:
    """The statically-sequenced baseline: fixed-order averaging."""
    n = len(per_rank_grads)
    avg = jax.tree_util.tree_map(
        lambda *xs: sum(x.astype(jnp.float32) for x in xs) / n,
        *per_rank_grads)
    return [jax.tree_util.tree_map(
        lambda a, t: a.astype(t.dtype), avg, per_rank_grads[0])
        for _ in range(n)]
