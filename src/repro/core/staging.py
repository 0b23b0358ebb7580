"""Device-resident staging engine: the bulk heap-I/O fast path.

Host-side submit-time staging used to dominate end-to-end cost (~100 ms
per 8-rank iteration at 16k elems, ROADMAP): every ``write_input`` was a
Python chunk loop plus a full-heap device round trip, and the "bulk"
variants still mirrored the whole ``[R, H]`` heap through host memory in
both directions.  This module replaces that with the registration-time
index maps of :class:`repro.core.tables.StaticTables` (``stage_in_map`` /
``stage_out_map``) and per-write-set compiled staging plans:

* **write**: ONE host->device transfer of the concatenated logical
  payloads; the pack transform into the padded chunk layout runs
  on-device, and zero-fills every pad of every written span as part of
  the same program, so stale heap data can never leak into the padded
  slices of chunked collectives.  At plan-build time each (rank,
  collective) entry's map is split into RUNS: maximal stretches filled
  from consecutive logical positions, and stretches of pads.  Where no
  entry has more live runs than chunks (every chunked, ragged and
  pad-free layout) the pack is static contiguous slices of the payload
  and zero blocks — a copy, with no index arrays on the device.  Only
  entries whose maps split finer (the ``in_perm`` granule transposes of
  composite all-to-alls) keep the element gather over device-resident
  maps.  The packed segments land in ``heap_in`` via buffer-donated
  device updates — in place on backends that implement donation
  (CPU/GPU/TPU in current jaxlibs), never a host heap mirror.
* **read**: the mirror path out of ``heap_out``: device segment slices
  fused into one buffer, ONE device->host transfer, and a vectorized
  un-pad.  Results are owned writable copies (never views aliasing the
  heap snapshot), so callers may mutate them freely.

Plans — the compiled program, plus the gather's device-resident index
arrays where it has them — are cached by the (rank, collective,
base-offset) signature of the write/read set, so a steady-state training
step (identical buckets every iteration) compiles once and thereafter
only ships payload values.  At plan-build time adjacent heap regions are
COALESCED: the runtime's split in/out allocation arenas pack registered
buffers contiguously, so a grad-sync step that stages every bucket
collapses to one stacked ``[R, W]`` block — a single
``dynamic_update_slice`` (write; a run plan stacks one packed row per
rank) / ``dynamic_slice`` (read) instead of
one op per (rank, collective).  Cost therefore scales with payload BYTES,
not with heap size or Python chunk-loop iterations.

Index maps are relative to each collective's base heap offset; per-SQE
dynamic buffer offsets (paper Sec. 3.1.2) are honored by adding the
override as a scalar at plan-build time.  Writes in one batch touching
overlapping regions (possible only via offset overrides) apply in
(rank, offset)-sorted order, not submission order.

Donation caveat: each write invalidates the PREVIOUS ``heap_in`` buffer.
The runtime immediately replaces its state, so this is only observable
to callers that squirrel away a stale ``DaemonState`` and poke its
``heap_in`` after a later write — don't.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import trace
from .config import OcclConfig
from .state import DaemonState
from .tables import StaticTables

# On the CPU backend ``np.asarray`` of a device array is a ZERO-COPY view
# (host memory IS device memory), so the read path needs no jit dispatch
# at all: un-pad directly out of the view with the precomputed maps and
# hand back owned copies.  Accelerator backends keep the compiled
# segment-gather plan (one fused device slice, one D2H transfer).
# Probed LAZILY on first read: importing this module must not initialize
# the jax backend (that would freeze platform selection before user code
# can call jax.config.update), and by first read the backend in use is
# the one the heaps actually live on.
@functools.lru_cache(maxsize=None)
def _host_is_device() -> bool:
    return jax.default_backend() == "cpu"


def _merge_segments(segs):
    """Coalesce (rank, off, span) runs that are adjacent in the heap.
    ``segs`` must be (rank, off)-sorted; returns the merged list."""
    merged = []
    for rank, off, span in segs:
        if merged and merged[-1][0] == rank \
                and merged[-1][1] + merged[-1][2] == off:
            r, o, s = merged[-1]
            merged[-1] = (r, o, s + span)
        else:
            merged.append((rank, off, span))
    return merged


def _stacked(merged) -> Optional[tuple]:
    """(r0, off, span) if the merged segments form one dense rank-range
    block — identical column window on consecutive ranks — which executes
    as a single 2D slice/update; None otherwise."""
    if not merged:
        return None
    offs = {(o, s) for _, o, s in merged}
    ranks = [r for r, _, _ in merged]
    if len(offs) == 1 and ranks == list(range(ranks[0],
                                               ranks[0] + len(ranks))):
        _, off, span = merged[0]
        return ranks[0], off, span
    return None


def _entry_runs(m: np.ndarray, span: int, n_chunks: int, lo: int):
    """One entry's padded span as runs in position order: ``(n, j)`` for
    ``n`` positions filled from logical positions ``j, j + 1, ...`` (``lo``
    is the entry's first), ``(n, None)`` for ``n`` pads.  None where the
    live map splits into more runs than the span has chunks (an
    ``in_perm`` granule transpose): the entry then needs the gather."""
    live = []
    if m.size:
        starts = np.flatnonzero(np.diff(m) != 1) + 1
        if starts.size >= n_chunks:
            return None
        starts = np.concatenate(([0], starts))
        sizes = np.diff(np.append(starts, m.size))
        live = sorted(zip(m[starts].tolist(), sizes.tolist(),
                          (lo + starts).tolist()))
    runs, pos = [], 0
    for off, n, j in live:
        if off > pos:
            runs.append((off - pos, None))
        runs.append((n, j))
        pos = off + n
    if span > pos:
        runs.append((span - pos, None))
    return runs


def _run_rows(t: StaticTables, sig) -> Optional[list]:
    """The (rank, base)-sorted write set as one list of runs per rank, in
    packed order, adjacent runs coalesced (pad onto pad, or live onto the
    live run whose logical end it continues); None where any entry needs
    the gather."""
    rows, logical, last_rank = [], 0, None
    for rank, cid, _ in sig:
        m = t.stage_in_map[cid]
        span = int(t.in_span[cid])
        runs = _entry_runs(m, span, span // int(t.chunk_pad[cid]), logical)
        if runs is None:
            return None
        if rank != last_rank:
            rows.append([])
            last_rank = rank
        row = rows[-1]
        for n, j in runs:
            if row and (j is None) == (row[-1][1] is None) and (
                    j is None or row[-1][1] + row[-1][0] == j):
                row[-1] = (row[-1][0] + n, row[-1][1])
            else:
                row.append((n, j))
        logical += m.size
    return rows


def _is_copy(rows) -> bool:
    """The packed set IS the payload: live runs only, in logical order."""
    pos = 0
    for n, j in (run for row in rows for run in row):
        if j != pos:
            return False
        pos += n
    return True


def _pack_runs(vals, runs, dtype):
    """Runs as packed positions: static slices of the flat payload and
    zero pads, concatenated."""
    return jnp.concatenate([jnp.zeros(n, dtype) if j is None
                            else vals[j:j + n] for n, j in runs])


def _gather_maps(t: StaticTables, sig):
    """The element gather's maps over the concatenated padded spans: the
    logical source of each position, and whether it is live (pads are
    zero-filled)."""
    src, mask = [], []
    logical = 0
    for _, cid, _ in sig:
        span = int(t.in_span[cid])
        m = t.stage_in_map[cid]
        s = np.zeros(span, np.int32)
        s[m] = logical + np.arange(m.size, dtype=np.int32)
        ok = np.zeros(span, bool)
        ok[m] = True
        src.append(s)
        mask.append(ok)
        logical += m.size
    return np.concatenate(src), np.concatenate(mask)


@dataclasses.dataclass
class _WritePlan:
    fn: Callable             # (heap, vals, gather_src, mask) -> heap
    # Device-resident, uploaded once per plan, and only for the element
    # gather (``path == "gather"``: an entry's live map splits into more
    # runs than chunks, as ``in_perm`` transposes do).  None for run plans
    # (static slices of ``vals`` and zero blocks, one run per chunk at
    # most: chunked, ragged and pad-free layouts) and where the sharded
    # path replaces ``fn``.
    gather_src: Optional[jnp.ndarray]
    mask: Optional[jnp.ndarray]
    # Sharded fast path (mesh backend): when the write set is one dense
    # full-rank stacked block, the packed payload is placed PER DEVICE via
    # jax.device_put with the heap's NamedSharding and the update runs
    # shard-locally — no [R, ...] gather, no cross-device payload
    # broadcast.  None when the engine has no sharding or the set is not
    # a full-rank block (the general plan stays correct on any backend).
    sharded_fn: Optional[Callable] = None   # (heap, block [R, span]) -> heap
    # Host copies of the gather's maps, for the sharded path's host-side
    # pack; None on run plans, and on a sharded set that is the payload
    # itself (pad-free, in logical order).
    src_np: Optional[np.ndarray] = None
    mask_np: Optional[np.ndarray] = None
    path: str = "gather"    # "runs" | "gather" | "sharded"


@dataclasses.dataclass
class _ReadPlan:
    fn: Callable             # heap -> packed padded segments [S]
    # (rank, coll_id, base) -> (packed position, logical size, unpad map
    # or None for the pad-free identity layout)
    slot_by_key: dict


class StagingEngine:
    """Pack/unpack between logical user payloads and the padded heap
    layout, via precomputed index maps and per-signature compiled plans."""

    def __init__(self, cfg: OcclConfig, tables: StaticTables,
                 sharding=None):
        self.cfg = cfg
        self.t = tables
        # Host-side payloads are cast to the HEAP dtype before upload, so
        # the transfer ships heap-width bytes (half for bfloat16 wire
        # compression) and non-float32 heaps never round-trip through
        # float32 (ml_dtypes supplies the numpy bfloat16).
        self._dtype = np.dtype(jnp.zeros((), cfg.dtype).dtype)
        self._write_plans: dict = {}
        self._read_plans: dict = {}
        # Mesh backend: the [R, ...] heap's NamedSharding (leading axis on
        # the mesh's rank axis).  Full-rank stacked writes then stage via
        # per-device jax.device_put placements instead of the sim-style
        # single-device payload commit (see _WritePlan.sharded_fn).
        self.sharding = sharding
        # Flush observability (BENCH_collectives.json "mesh" section):
        # payload bytes shipped by write() vs what a full [R, heap] mirror
        # would move, and how many writes took the sharded placement path.
        self.flush_writes = 0
        self.flush_bytes = 0
        self.sharded_flushes = 0
        # Writes that took the element gather (an ``in_perm`` layout):
        # every other layout packs as contiguous run copies.
        self.gather_flushes = 0
        # Write and read plans built on a cache miss (each compiles on its
        # first call): 0 a step once the sizes have been seen.
        self.plan_builds = 0

    # -- writes ----------------------------------------------------------
    def _write_plan(self, sig) -> _WritePlan:
        """``sig`` is the (rank, base)-SORTED (rank, coll_id, base) tuple,
        so every caller-order permutation of one write set hits one plan
        (one compile, one LRU slot)."""
        plan = self._write_plans.pop(sig, None)
        if plan is not None:
            self._write_plans[sig] = plan    # touch: LRU re-insert
            return plan
        self.plan_builds += 1
        nbytes = sum(int(self.t.in_log[cid]) for _, cid, _ in sig) * \
            self._dtype.itemsize
        with trace.span("plan_build", kind="write", bytes=nbytes) as sp:
            plan = self._build_write_plan(sig)
            sp.set_metadata(path=plan.path)
        if len(self._write_plans) > 64:    # evict least-recently-used
            self._write_plans.pop(next(iter(self._write_plans)))
        self._write_plans[sig] = plan
        return plan

    def _build_write_plan(self, sig) -> _WritePlan:
        t = self.t
        segs = [(int(rank), int(base), int(t.in_span[cid]))
                for rank, cid, base in sig]
        merged = _merge_segments(segs)
        stack = _stacked(merged)
        # One row of runs per rank; None where an entry needs the gather.
        rows = _run_rows(t, sig)
        # Sharded fast path: a dense block covering EVERY rank with one
        # identical column window (the grad-sync / all-ranks-submit shape)
        # updates shard-locally after per-device payload placement.
        sharded_fn = None
        if (self.sharding is not None and stack is not None
                and stack[0] == 0 and len(merged) == self.cfg.n_ranks):
            s_off = stack[1]

            @functools.partial(jax.jit, donate_argnums=(0,))
            def sharded_fn(heap, block):
                return jax.lax.dynamic_update_slice(heap, block, (0, s_off))

        path = ("sharded" if sharded_fn is not None else
                "runs" if rows is not None else "gather")
        # The sharded host pack needs the maps unless the set is the
        # payload itself (pad-free, in logical order).
        src = mask = None
        if path == "gather" or (path == "sharded" and not (
                rows is not None and _is_copy(rows))):
            src, mask = _gather_maps(t, sig)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def fn(heap, vals, gather_src, ok):
            vals = vals.astype(heap.dtype)
            if stack is not None:
                if rows is None:
                    block = jnp.where(ok, vals[gather_src], 0).reshape(
                        -1, stack[2])
                else:
                    # One packed row per rank, stacked: no reshape of the
                    # flat payload (a relayout pass on the TPU).
                    block = jnp.stack([_pack_runs(vals, row, heap.dtype)
                                       for row in rows])
                return jax.lax.dynamic_update_slice(heap, block, stack[:2])
            if rows is None:
                packed = jnp.where(ok, vals[gather_src], 0)
            else:
                packed = _pack_runs(vals, [run for row in rows for run in row],
                                    heap.dtype)
            o = 0
            for rank, off, span in merged:
                heap = jax.lax.dynamic_update_slice(
                    heap, packed[o:o + span][None, :], (rank, off))
                o += span
            return heap

        # Only the element gather reads the maps on the device; the sharded
        # path packs on the host from them.  Run and pad-free plans upload
        # nothing (the maps are as large as the write set itself, in int32).
        on_device = path == "gather"
        return _WritePlan(fn=fn,
                          gather_src=jnp.asarray(src) if on_device else None,
                          mask=jnp.asarray(mask) if on_device else None,
                          sharded_fn=sharded_fn, src_np=src, mask_np=mask,
                          path=path)

    def snapshot(self, coll_id: int, data) -> np.ndarray:
        """Validate one logical payload and return an OWNED heap-dtype
        copy — the single definition of the payload invariant, shared by
        the write path and the runtime's submit-time staging (which must
        capture the value at call time, not at flush time).  The copy
        also keeps caller memory out of the (async) jit below."""
        data = np.ravel(data)
        want = int(self.t.in_log[coll_id])
        if data.size != want:
            # ValueError, not assert: a silently-undersized payload would
            # gather clamped tail garbage into the heap under python -O.
            raise ValueError(
                f"collective {coll_id} input: got {data.size} elems, "
                f"registered logical size is {want}")
        return np.array(data, dtype=self._dtype)   # np.array always copies

    def write(self, state: DaemonState, items,
              owned: bool = False) -> DaemonState:
        """items: iterable of ``(rank, coll_id, data, base_in_off)``.
        Logical payloads land at their padded positions, pads are zeroed,
        in one transfer + one donated in-place scatter program.
        ``owned=True`` (the staged-submit flush, whose payloads were
        already snapshotted at submit time) skips the defensive
        anti-aliasing copy on the per-step hot path."""
        items = list(items)
        if not items:
            return state
        datas = [data if owned else self.snapshot(cid, data)
                 for _, cid, data, _ in items]
        # Stable (rank, base) sort: the plan cache is permutation-
        # independent, and duplicate-region writes keep caller order
        # (last write wins) among themselves.
        order = sorted(range(len(items)),
                       key=lambda i: (items[i][0], items[i][3]))
        plan = self._write_plan(
            tuple((items[i][0], items[i][1], items[i][3]) for i in order))
        vals = [datas[i] for i in order]
        vals = vals[0] if len(vals) == 1 else np.concatenate(vals)
        self.flush_writes += 1
        self.flush_bytes += vals.nbytes
        if plan.sharded_fn is not None:
            # Mesh fast path: pack host-side with the same precomputed
            # maps, then device_put the [R, span] block with the heap's
            # NamedSharding — each device receives ONLY its own rank's
            # rows, and the donated update runs shard-locally (the
            # sim-style path would commit the whole payload to one device
            # and let SPMD re-distribute it).
            packed = vals if plan.src_np is None else vals[plan.src_np]
            if plan.src_np is not None:
                packed[~plan.mask_np] = packed.dtype.type(0)
            block = jax.device_put(
                packed.reshape(self.cfg.n_ranks, -1), self.sharding)
            heap = plan.sharded_fn(state.heap_in, block)
            self.sharded_flushes += 1
            return state._replace(heap_in=heap)
        # vals is passed as numpy in the HEAP dtype: the jit commits it
        # inside the one dispatch (zero-copy on CPU; one heap-width H2D
        # transfer on accelerators).
        heap = plan.fn(state.heap_in, vals, plan.gather_src, plan.mask)
        self.gather_flushes += plan.path == "gather"
        return state._replace(heap_in=heap)

    # -- reads -----------------------------------------------------------
    def _read_plan(self, sig) -> _ReadPlan:
        """``sig`` is the (rank, base)-SORTED (rank, coll_id, base) tuple
        (permutation-independent plan cache, like writes)."""
        plan = self._read_plans.pop(sig, None)
        if plan is not None:
            self._read_plans[sig] = plan     # touch: LRU re-insert
            return plan
        self.plan_builds += 1
        nbytes = sum(int(self.t.out_span[cid]) for _, cid, _ in sig) * \
            self._dtype.itemsize
        with trace.span("plan_build", kind="read", bytes=nbytes):
            plan = self._build_read_plan(sig)
        if len(self._read_plans) > 64:     # evict least-recently-used
            self._read_plans.pop(next(iter(self._read_plans)))
        self._read_plans[sig] = plan
        return plan

    def _build_read_plan(self, sig) -> _ReadPlan:
        t = self.t
        segs, slot_by_key = [], {}
        pos = 0
        for rank, cid, base in sig:
            span = int(t.out_span[cid])
            segs.append((int(rank), int(base), span))
            m = t.stage_out_map[cid]
            identity = bool(
                (m == np.arange(m.size, dtype=np.int32)).all())
            slot_by_key[(rank, cid, base)] = (
                pos, m.size, None if identity else m)
            pos += span
        merged = _merge_segments(segs)
        stack = _stacked(merged)

        @jax.jit
        def fn(heap):
            if stack is not None:
                r0, off, span = stack
                n_rows = len(merged)
                return jax.lax.dynamic_slice(
                    heap, (r0, off), (n_rows, span)).ravel()
            return jnp.concatenate([
                jax.lax.dynamic_slice(heap, (rank, off), (1, span)).ravel()
                for rank, off, span in merged])

        return _ReadPlan(fn=fn, slot_by_key=slot_by_key)

    def read(self, state: DaemonState, keys) -> dict:
        """keys: iterable of ``(rank, coll_id, base_out_off)``.  Returns
        ``{(rank, coll_id): logical output}`` as owned writable arrays."""
        keys = list(keys)
        if not keys:
            return {}
        if _host_is_device():
            return self._read_host(state, keys)
        plan = self._read_plan(
            tuple(sorted(keys, key=lambda k: (k[0], k[2]))))
        packed = np.asarray(plan.fn(state.heap_out))
        out = {}
        for rank, cid, base in keys:
            pos, n, unpad = plan.slot_by_key[(rank, cid, base)]
            if unpad is None:
                out[(rank, cid)] = packed[pos:pos + n].copy()
            else:
                out[(rank, cid)] = packed[pos + unpad]
        return out

    def _read_host(self, state: DaemonState, keys) -> dict:
        """CPU fast path: un-pad straight out of the zero-copy heap view —
        no jit dispatch, no transfer; per-key copies stay owned."""
        t = self.t
        heap = np.asarray(state.heap_out)
        out = {}
        for rank, cid, base in keys:
            m = t.stage_out_map[cid]
            row = heap[rank]
            if m.size == int(t.out_span[cid]):      # pad-free: identity map
                out[(rank, cid)] = row[base:base + m.size].copy()
            else:
                out[(rank, cid)] = row[base + m]    # fancy-index: owned
        return out
