"""The daemon loop: a long-running jitted superstep loop (paper Sec. 3.1).

Two interchangeable backends share the identical per-rank scheduler core:

* **sim** — all ranks live on one device; per-rank state carries a leading
  rank axis and the superstep is ``vmap``-ed; the connector fabric is a
  gather along the communicator ring permutation.  Used by unit/property
  tests and the collective microbenchmarks.

* **mesh** — ranks are devices of a mesh axis under ``shard_map``; the
  fabric is a pair of ``lax.ppermute`` s (forward slice + reverse credit)
  per lane per superstep.  The communication schedule is *static* — which
  collective's slice rides the wire is the dynamic, per-device scheduler
  decision.  Deadlock at the transport layer is therefore structurally
  impossible; the scheduler provides liveness (preemption) and performance
  (stickiness/gang convergence).

The loop terminates on: all work drained, the voluntary-quit threshold
(consecutive fabric-wide no-progress supersteps, Sec. 3.1.3), or the hard
superstep budget.  The host relaunches it event-driven while completions
lag submissions.

Launch prologue (both backends): the per-launch clock ``launch_steps`` and
the no-progress counter are zeroed, the launch counter ``epoch`` advances,
and active task-queue arrivals are rebased onto the fresh launch clock
(scheduler.rebase_arrivals).  The superstep budget bounds ``launch_steps``
— a PER-LAUNCH quantity — so the quit/relaunch cycle can repeat forever;
the cumulative ``supersteps`` epoch clock is observability-only.

The tick contract (compute-communication overlap)
-------------------------------------------------
``tick(state, k)`` is the unit of daemon progress: a PURE, jit-composable
function advancing up to ``k`` supersteps of the exact loop body above and
returning ``(state, TickFlags)``.  It is callable from *inside* a traced
training step — the mailbox fields of :class:`DaemonState` persist
in-flight wire messages across tick boundaries, so suspending after any
superstep and resuming later is exactly the voluntary-quit/relaunch cycle
the paper already requires, at a finer grain.  The contract:

* **Purity.**  ``tick`` closes over static tables only; all dynamic state
  threads through the ``DaemonState`` argument.  No host callbacks, no
  side effects — safe under ``jit``, ``lax.while_loop`` and ``custom_vjp``
  backward passes.
* **Batching invariance.**  ``tick(st, a)`` then ``tick(st, b)`` is
  bit-identical to ``tick(st, a + b)`` (the mailbox load/store round trip
  at the boundary is the identity), so a host ``drive()`` launch and any
  in-step tick batching produce the SAME superstep/preemption trajectory.
* **drive() is a thin wrapper.**  A daemon launch IS
  ``launch_prologue`` + ``tick(superstep_budget + 1)``; the host loop
  only packs SQEs and reconciles CQEs around it.  ``drive()`` remains the
  right entry point for host-driven workloads (registration-time payload
  staging, callbacks, DeadlockTimeout patience); in-step submission uses
  :mod:`repro.core.device_api`.
* **Accounting.**  Each tick stamps its supersteps into
  ``overlap_steps`` or ``barrier_steps`` by its static ``barrier`` flag —
  barrier ticks are supersteps the step is *blocked* on (drive()/drain),
  overlap ticks hide behind compute — and ``overlap + barrier ==
  supersteps`` always.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import OcclConfig
from .scheduler import (
    LocalTables,
    Mailbox,
    SharedTables,
    chain_relink_fired,
    rank_superstep,
    rebase_arrivals,
)
from .state import DaemonState
from .tables import StaticTables


def shared_tables(t: StaticTables) -> SharedTables:
    return SharedTables(
        registered=jnp.asarray(t.registered),
        kind=jnp.asarray(t.kind),
        op=jnp.asarray(t.op),
        lane=jnp.asarray(t.lane),
        n_steps=jnp.asarray(t.n_steps),
        n_slices=jnp.asarray(t.n_slices),
        n_rounds=jnp.asarray(t.n_rounds),
        in_chunked=jnp.asarray(t.in_chunked),
        out_chunked=jnp.asarray(t.out_chunked),
        base_in_off=jnp.asarray(t.base_in_off),
        base_out_off=jnp.asarray(t.base_out_off),
        next_coll=jnp.asarray(t.next_coll),
        chain_tail=jnp.asarray(t.chain_tail),
        chain_prio_inherit=jnp.asarray(t.chain_prio_inherit),
        chain_mask=jnp.asarray(t.chain_mask),
        chain_src=jnp.asarray(t.chain_src),
        chain_dst=jnp.asarray(t.chain_dst),
        lane_caps=jnp.asarray(t.lane_caps),
    )


def local_tables(t: StaticTables) -> LocalTables:
    """Per-rank tables with leading rank axis (sim) — slice [r] for mesh."""
    return LocalTables(
        member=jnp.asarray(t.member),
        prog_kind=jnp.asarray(t.prog_kind),
        prog_chunk=jnp.asarray(t.prog_chunk),
        chain_next=jnp.asarray(t.chain_next),
        chain_tail_r=jnp.asarray(t.chain_tail_r),
    )


def _sim_exchange(fwd_src, rev_src, outbox: Mailbox) -> Mailbox:
    """Deliver per-lane messages along each communicator ring (sim backend).

    ``outbox`` fields have shape [R, L, ...]; the message arriving at rank
    r on lane l was sent by ``fwd_src[l, r]`` (resp. ``rev_src``).  One
    batched gather over the (rank, lane) grid per field — no Python lane
    loop in the compiled superstep.
    """
    L = fwd_src.shape[0]
    lanes = jnp.arange(L)

    def pick(field, src):  # field: [R, L, ...] -> gathered [R, L, ...]
        return field[src.T, lanes[None, :]]

    return Mailbox(
        fwd_count=pick(outbox.fwd_count, fwd_src),
        fwd_coll=pick(outbox.fwd_coll, fwd_src),
        fwd_payload=pick(outbox.fwd_payload, fwd_src),
        rev_count=pick(outbox.rev_count, rev_src),
        rev_coll=pick(outbox.rev_coll, rev_src),
    )


def _pack16_to_i32(pay: jnp.ndarray, pad: int) -> jnp.ndarray:
    """Bitcast PAIRS of adjacent 16-bit payload elements into i32 lanes.

    ``pay`` is [G, W] of a 2-byte dtype; an odd W is zero-padded by ``pad``
    (0 or 1) so every element has a pair partner.  Returns [G, (W+pad)//2]
    i32 — exact bits, concatenable with the i32 (coll, count) header.
    The payload is bitcast to u16 FIRST: a pad/concatenate in a float
    dtype may canonicalize NaN payloads (0x7f81 -> 0x7fc0 in bf16), while
    integer data movement carries every bit pattern.
    """
    bits = jax.lax.bitcast_convert_type(pay, jnp.uint16)
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((bits.shape[0], pad), jnp.uint16)], axis=1)
    return jax.lax.bitcast_convert_type(
        bits.reshape(bits.shape[0], -1, 2), jnp.int32)


def _unpack16_from_i32(packed: jnp.ndarray, dtype, width: int) -> jnp.ndarray:
    """Inverse of :func:`_pack16_to_i32`: [G, P] i32 -> [G, width] 16-bit
    (the pad element, if any, is sliced off in the u16 domain)."""
    pairs = jax.lax.bitcast_convert_type(packed, jnp.uint16)   # [G, P, 2]
    bits = pairs.reshape(pairs.shape[0], -1)[:, :width]
    return jax.lax.bitcast_convert_type(bits, dtype)


def _mesh_exchange(t: StaticTables, outbox: Mailbox, axis_name: str) -> Mailbox:
    """Deliver messages over the device fabric (mesh backend).

    Lanes whose communicators share a ring permutation are FUSED: their
    stacked traffic rides one ppermute pair per direction — the forward
    direction packs (coll, count) headers and the [B, SL] payload burst of
    every fused lane into a single i32 buffer (exact bitcast for 32-bit
    heap dtypes; for 16-bit dtypes adjacent payload-element PAIRS are
    bitcast into i32 lanes per the registration-time
    ``lane_group_pack16`` pairing metadata, odd lane zero-padded), the
    reverse direction is one i32 credit-header ppermute.  With one
    communicator ring (the common case) the whole superstep costs exactly
    two ppermutes for BOTH 32-bit and 16-bit heaps, vs five per lane in
    the unfused scheme; ``cfg.packed_16bit=False`` (tables built without
    pairing metadata) restores the separate header/payload ppermutes for
    16-bit dtypes (three per superstep).
    """
    L, B, SL = outbox.fwd_payload.shape
    dt = outbox.fwd_payload.dtype
    fuse_payload = dt.itemsize == 4
    pack16 = t.lane_group_pack16 if dt.itemsize == 2 else None

    fwd_count = jnp.zeros_like(outbox.fwd_count)
    fwd_coll = jnp.zeros_like(outbox.fwd_coll)
    fwd_payload = jnp.zeros_like(outbox.fwd_payload)
    rev_count = jnp.zeros_like(outbox.rev_count)
    rev_coll = jnp.zeros_like(outbox.rev_coll)

    for gi, (group_lanes, fwd_pairs, rev_pairs) in enumerate(t.lane_groups):
        g = jnp.asarray(group_lanes)
        hdr = jnp.stack([outbox.fwd_coll[g], outbox.fwd_count[g]], axis=1)
        pay = outbox.fwd_payload[g].reshape(len(group_lanes), B * SL)
        if fuse_payload:
            # Single fwd ppermute: header ++ bitcast payload, all lanes.
            packed = jnp.concatenate(
                [hdr, jax.lax.bitcast_convert_type(pay, jnp.int32)
                 if dt != jnp.int32 else pay], axis=1)
            moved = jax.lax.ppermute(packed, axis_name, perm=fwd_pairs)
            got_hdr, got_pay = moved[:, :2], moved[:, 2:]
            if dt != jnp.int32:
                got_pay = jax.lax.bitcast_convert_type(got_pay, dt)
        elif pack16 is not None:
            # Packed 16-bit: element pairs ride i32 lanes alongside the
            # header in the SAME single fwd ppermute.
            cols, pad = pack16[gi]
            packed = jnp.concatenate([hdr, _pack16_to_i32(pay, pad)], axis=1)
            moved = jax.lax.ppermute(packed, axis_name, perm=fwd_pairs)
            got_hdr = moved[:, :2]
            got_pay = _unpack16_from_i32(moved[:, 2:2 + cols], dt, B * SL)
        else:
            got_hdr = jax.lax.ppermute(hdr, axis_name, perm=fwd_pairs)
            got_pay = jax.lax.ppermute(pay, axis_name, perm=fwd_pairs)
        fwd_coll = fwd_coll.at[g].set(got_hdr[:, 0])
        fwd_count = fwd_count.at[g].set(got_hdr[:, 1])
        fwd_payload = fwd_payload.at[g].set(
            got_pay.astype(dt).reshape(len(group_lanes), B, SL))

        rhdr = jnp.stack([outbox.rev_coll[g], outbox.rev_count[g]], axis=1)
        rgot = jax.lax.ppermute(rhdr, axis_name, perm=rev_pairs)
        rev_coll = rev_coll.at[g].set(rgot[:, 0])
        rev_count = rev_count.at[g].set(rgot[:, 1])

    return Mailbox(
        fwd_count=fwd_count, fwd_coll=fwd_coll, fwd_payload=fwd_payload,
        rev_count=rev_count, rev_coll=rev_coll,
    )


def _drained(st: DaemonState) -> jnp.ndarray:
    """All submitted work complete on this rank (reductions over [C])."""
    return ((st.sq_read >= st.sq_size)
            & ~jnp.any(st.tq_active)
            & ~jnp.any(st.inflight))


class TickFlags(NamedTuple):
    """Progress report of one ``tick(state, k)`` call.

    ``steps`` is how many supersteps actually ran (< k when the launch
    went not-live first); ``live`` is the fabric-wide continue flag after
    the tick (False: drained, voluntary quit, or budget — re-run
    ``launch_prologue`` before ticking again); ``drained`` is True when
    every rank's submitted work is complete."""

    steps: jnp.ndarray    # [] i32
    live: jnp.ndarray     # [] bool
    drained: jnp.ndarray  # [] bool


def launch_prologue(st: DaemonState) -> DaemonState:
    """Pure launch prologue (both backends; shape-generic over the leading
    rank axis): fresh launch clock + epoch tick + bounded queue-age rebase
    (see module docstring).  Does NOT touch SQ/CQ cursors — those belong
    to the submission boundary (sqcq.HostQueues.pack_sq host-side,
    device_api.device_prologue in-trace)."""
    st = st._replace(
        global_live=jnp.ones_like(st.global_live),
        no_prog=jnp.zeros_like(st.no_prog),
        launch_steps=jnp.zeros_like(st.launch_steps),
        epoch=st.epoch + 1,
    )
    return rebase_arrivals(st)


def _tick_accounting(st: DaemonState, steps: jnp.ndarray,
                     barrier: bool) -> DaemonState:
    """Stamp one tick's supersteps into the barrier/overlap split."""
    if barrier:
        return st._replace(tick_calls=st.tick_calls + 1,
                           barrier_steps=st.barrier_steps + steps)
    return st._replace(tick_calls=st.tick_calls + 1,
                       overlap_steps=st.overlap_steps + steps)


def _relink_edges(t: StaticTables) -> tuple:
    """Static per-edge relink descriptors for the sim daemon.

    Each chain edge c -> next_coll[c] rewrites the successor's contiguous
    input span ``heap_in[dst_lo : dst_lo + span]`` from a build-time-known
    gather of ``heap_out`` (tables._build_chain_links).  Because every
    offset is static, the sim daemon can apply the hand-off as a cheap
    static-slice + ``where``-select per superstep — no dynamic scatter, no
    cond over the heap.  When the source map is itself one contiguous run
    (the common chunk hand-off), the gather degrades to a static slice.

    Returns a hashable tuple of
    ``(c, dst_lo, span, ('slice', src_lo, n) | ('gather', idx_bytes))``
    entries (part of the jit-cache key alongside the config).
    """
    edges = []
    C = t.chain_dst.shape[0]
    for c in range(C):
        dst = t.chain_dst[c]
        valid = dst < (1 << 30)
        if not valid.any():
            continue
        span = int(valid.sum())
        dst_lo = int(dst[0])
        src = t.chain_src[c, :span]
        live = src >= 0
        n = int(live.sum())
        contiguous = (n > 0 and bool(live[:n].all())
                      and np.array_equal(src[:n],
                                         src[0] + np.arange(n, dtype=src.dtype)))
        if contiguous:
            desc = ("slice", int(src[0]), n)
        else:
            desc = ("gather", src.tobytes())
        edges.append((c, dst_lo, span, desc))
    return tuple(edges)


# One compiled daemon per (OcclConfig, relink edges) (tables are
# ARGUMENTS, so different registrations / test instances with the same
# config share the binary; the static chain-edge descriptors are part of
# the key because they shape the in-body relink slices).
_SIM_JIT_CACHE: dict = {}


def _edge_plan(edges: tuple) -> list:
    """Unpack the static relink-edge descriptors (trace-time constants)."""
    plan = []
    for c, dst_lo, span, desc in edges:
        if desc[0] == "slice":
            plan.append((c, dst_lo, span, desc[1], desc[2], None))
        else:
            idx = np.frombuffer(desc[1], dtype=np.int32).copy()
            plan.append((c, dst_lo, span, None, None,
                         (jnp.asarray(np.maximum(idx, 0)),
                          jnp.asarray(idx >= 0))))
    return plan


def _sim_body_fn(cfg: OcclConfig, edges: tuple) -> Callable:
    """ONE sim superstep: vmapped scheduler + deferred relink + fabric
    exchange + liveness consensus.  Shared verbatim by ``tick`` and the
    host daemon — the single definition is what makes tick-mode
    trajectories bit-identical to drive()-mode."""
    edge_plan = _edge_plan(edges)

    def vstep(sh, lt, st, inbox):
        return jax.vmap(
            functools.partial(rank_superstep, cfg, sh, defer_relink=True),
            in_axes=(0, 0, 0), out_axes=(0, 0))(lt, st, inbox)

    def body(sh, lt, fwd_src, rev_src, st, inbox):
        prev_sc = st.stage_completions
        st, outbox = vstep(sh, lt, st, inbox)
        # Deferred chain relink, applied in-body from purely STATIC
        # slices: under the per-rank vmap a cond predicate is batched
        # (lowers to a select paying the O(M) hand-off gather every
        # superstep), and a scalar-predicate cond touching the heap
        # in this hot body costs a full heap copy per superstep (XLA
        # loses carry aliasing at the loop back-edge).  Instead each
        # chain edge rewrites the successor's contiguous input span
        # with a static-slice + ``where``-select keyed on "did this
        # rank complete the predecessor this superstep" — a few KB of
        # vectorized traffic per superstep, no scatter, no cond.
        if edge_plan:
            fired = jax.vmap(chain_relink_fired,
                             in_axes=(None, 0, 0, 0))(
                sh, lt, prev_sc, st.stage_completions)
            heap_in, heap_out = st.heap_in, st.heap_out
            for c, dst_lo, span, src_lo, n, gather in edge_plan:
                if gather is None:
                    vals = heap_out[:, src_lo:src_lo + n]
                    if n < span:            # zero-filled pad tail
                        vals = jnp.concatenate(
                            [vals, jnp.zeros((vals.shape[0],
                                              span - n), vals.dtype)],
                            axis=1)
                else:
                    idx, live = gather
                    vals = jnp.where(live[None, :],
                                     heap_out[:, idx], 0)
                cur = heap_in[:, dst_lo:dst_lo + span]
                new = jnp.where(fired[:, c][:, None],
                                vals.astype(cur.dtype), cur)
                heap_in = heap_in.at[:, dst_lo:dst_lo + span].set(new)
            st = st._replace(heap_in=heap_in)
        inbox = _sim_exchange(fwd_src, rev_src, outbox)
        all_drained = jnp.all(jax.vmap(_drained)(st))
        quit_now = jnp.min(st.no_prog) >= cfg.quit_threshold
        over_budget = st.launch_steps[0] >= cfg.superstep_budget
        live = ~(all_drained | quit_now | over_budget)
        st = st._replace(
            global_live=jnp.broadcast_to(live, st.global_live.shape))
        return st, inbox

    return body


def _sim_tick_fn(cfg: OcclConfig, edges: tuple, barrier: bool) -> Callable:
    """tick(sh, lt, fwd_src, rev_src, st, k) -> (st, TickFlags), sim."""
    superstep = _sim_body_fn(cfg, edges)

    def tick(sh, lt, fwd_src, rev_src, st, k):
        def cond(carry):
            st, _, i = carry
            return st.global_live[0] & (i < k)

        def body(carry):
            st, inbox, i = carry
            st, inbox = superstep(sh, lt, fwd_src, rev_src, st, inbox)
            return st, inbox, i + jnp.int32(1)

        st, inbox, i = jax.lax.while_loop(
            cond, body, (st, _load_mailbox(st), jnp.int32(0)))
        st = _tick_accounting(_store_mailbox(st, inbox), i, barrier)
        flags = TickFlags(steps=i, live=st.global_live[0],
                          drained=jnp.all(jax.vmap(_drained)(st)))
        return st, flags

    return tick


def _sim_daemon_jit(cfg: OcclConfig, edges: tuple = ()) -> Callable:
    key = (cfg, edges)
    if key in _SIM_JIT_CACHE:
        return _SIM_JIT_CACHE[key]

    tick = _sim_tick_fn(cfg, edges, barrier=True)

    # The state is donated: a launch rewrites it in place, so the device
    # never holds two copies of the heaps (callers re-read ``rt.state``).
    @functools.partial(jax.jit, donate_argnums=(4,))
    def daemon(sh: SharedTables, lt: LocalTables, fwd_src, rev_src,
               st: DaemonState) -> DaemonState:
        # A launch IS prologue + one barrier tick.  k = budget + 1 never
        # binds — the in-body budget check flips ``global_live`` first —
        # so the trajectory is bit-identical to the pre-tick unbounded
        # while loop.
        st, _ = tick(sh, lt, fwd_src, rev_src, launch_prologue(st),
                     jnp.int32(cfg.superstep_budget + 1))
        return st

    _SIM_JIT_CACHE[key] = daemon
    return daemon


def build_sim_tick(cfg: OcclConfig, t: StaticTables,
                   barrier: bool = False) -> Callable:
    """Traceable ``tick(state, k) -> (state, TickFlags)``, sim backend
    (state leaves carry the leading [R] rank axis).

    NOT jitted: compose it inside a jitted training step (see
    :mod:`repro.core.device_api`) or wrap in ``jax.jit`` for host use.
    ``barrier`` is a STATIC accounting tag — True means the caller is
    blocked on this tick (drive()/drain), False means the tick is hidden
    behind compute; it does not change scheduling."""
    sh = shared_tables(t)
    lt = local_tables(t)
    fwd_src = jnp.asarray(t.fwd_src)
    rev_src = jnp.asarray(t.rev_src)
    fn = _sim_tick_fn(cfg, _relink_edges(t), barrier)
    return lambda st, k: fn(sh, lt, fwd_src, rev_src, st, k)


def _load_mailbox(st: DaemonState) -> Mailbox:
    """Re-inject messages that were on the wire at the last daemon exit."""
    return Mailbox(
        fwd_count=st.mb_fwd_count, fwd_coll=st.mb_fwd_coll,
        fwd_payload=st.mb_fwd_payload,
        rev_count=st.mb_rev_count, rev_coll=st.mb_rev_coll)


def _store_mailbox(st: DaemonState, inbox: Mailbox) -> DaemonState:
    return st._replace(
        mb_fwd_count=inbox.fwd_count, mb_fwd_coll=inbox.fwd_coll,
        mb_fwd_payload=inbox.fwd_payload,
        mb_rev_count=inbox.rev_count, mb_rev_coll=inbox.rev_coll)


def build_sim_daemon(cfg: OcclConfig, t: StaticTables) -> Callable:
    """Daemon for the sim backend: state [R,...] -> state."""
    sh = shared_tables(t)
    lt = local_tables(t)
    fwd_src = jnp.asarray(t.fwd_src)
    rev_src = jnp.asarray(t.rev_src)
    fn = _sim_daemon_jit(cfg, _relink_edges(t))
    return lambda st: fn(sh, lt, fwd_src, rev_src, st)


def build_shardmap_daemon(cfg: OcclConfig, t: StaticTables, mesh,
                          axis_name: str = "rank") -> Callable:
    """jit daemon over a real device mesh: state leaves are [R, ...]
    sharded along ``axis_name``; each device runs the per-rank scheduler
    and the connector fabric is a ppermute pair per lane per superstep."""
    from jax.sharding import PartitionSpec as P

    mesh_daemon = build_mesh_daemon(cfg, t, axis_name)

    def per_dev(st_slice: DaemonState) -> DaemonState:
        st1 = jax.tree_util.tree_map(lambda a: a[0], st_slice)
        st1 = mesh_daemon(st1)
        return jax.tree_util.tree_map(lambda a: a[None], st1)

    inner = jax.shard_map(per_dev, mesh=mesh, in_specs=P(axis_name),
                          out_specs=P(axis_name), check_vma=False)

    # Donated state, as in the sim daemon.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def daemon(st: DaemonState) -> DaemonState:
        return inner(st)

    return daemon


def _count_primitive(jaxpr, name: str) -> int:
    """Recursively count occurrences of primitive ``name`` in a jaxpr
    (descends into call/scan/shard_map sub-jaxprs via eqn params)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_primitive(inner, name)
    return n


def count_exchange_ppermutes(cfg: OcclConfig, n_comms: int = 1) -> int:
    """Trace one ``_mesh_exchange`` superstep and count its ppermute ops.

    The fusion structure depends only on the heap dtype, the packing
    metadata and the lane grouping — not on the ring size — so the trace
    runs on a single-device mesh (always available; tier-1 and the mesh
    perf record both use this without multi-device XLA flags).
    """
    import dataclasses as _dc

    from jax.sharding import PartitionSpec as P
    from .primitives import Communicator
    from .tables import build_tables

    cfg1 = _dc.replace(cfg, n_ranks=1, max_comms=max(cfg.max_comms, n_comms))
    comms = [Communicator(comm_id=i, members=(0,), lane=i)
             for i in range(n_comms)]
    t = build_tables(cfg1, comms, [])
    L, B, SL = cfg1.max_comms, cfg1.burst_slices, cfg1.slice_elems
    dt = jnp.dtype(cfg1.dtype)
    outbox = Mailbox(
        fwd_count=jnp.zeros((1, L), jnp.int32),
        fwd_coll=jnp.zeros((1, L), jnp.int32),
        fwd_payload=jnp.zeros((1, L, B, SL), dt),
        rev_count=jnp.zeros((1, L), jnp.int32),
        rev_coll=jnp.zeros((1, L), jnp.int32),
    )
    mesh = jax.make_mesh((1,), ("rank",))

    def per_dev(ob: Mailbox) -> Mailbox:
        ob1 = jax.tree_util.tree_map(lambda a: a[0], ob)
        out = _mesh_exchange(t, ob1, "rank")
        return jax.tree_util.tree_map(lambda a: a[None], out)

    fn = jax.shard_map(per_dev, mesh=mesh, in_specs=P("rank"),
                       out_specs=P("rank"), check_vma=False)
    closed = jax.make_jaxpr(fn)(outbox)
    return _count_primitive(closed.jaxpr, "ppermute")


def build_mesh_tick(cfg: OcclConfig, t: StaticTables, axis_name: str,
                    rank_of_device: np.ndarray | None = None,
                    barrier: bool = False) -> Callable:
    """Per-device ``tick(state, k) -> (state, TickFlags)`` for use inside
    ``shard_map``.

    ``rank_of_device`` maps the device's linear index along ``axis_name`` to
    its OCCL rank (identity by default).  The returned callable takes and
    returns the per-device DaemonState (no leading rank axis); static
    tables are indexed by the device's rank via ``lax.axis_index``.  The
    flags are replicated across devices by construction: ``live`` is the
    fabric consensus computed inside the body, ``steps`` follows the
    uniform loop cond, and ``drained`` is an explicit all_gather.
    """
    sh = shared_tables(t)
    lt_all = local_tables(t)  # leading rank axis; gathered per device
    if rank_of_device is None:
        rank_of_device = np.arange(cfg.n_ranks)
    rod = jnp.asarray(rank_of_device, jnp.int32)

    def tick(st: DaemonState, k) -> tuple[DaemonState, TickFlags]:
        dev = jax.lax.axis_index(axis_name)
        rank = rod[dev]
        lt = jax.tree_util.tree_map(lambda a: a[rank], lt_all)

        def cond(carry):
            st, _, i = carry
            return st.global_live & (i < k)

        def body(carry):
            st, inbox, i = carry
            st, outbox = rank_superstep(cfg, sh, lt, st, inbox,
                                        cond_relink=cfg.cond_chain_relink)
            inbox = _mesh_exchange(t, outbox, axis_name)
            # Fabric-wide consensus on liveness (computed in the body so the
            # cond stays collective-free).
            drained = jnp.all(
                jax.lax.all_gather(_drained(st), axis_name))
            stuck = jnp.all(
                jax.lax.all_gather(st.no_prog >= cfg.quit_threshold,
                                   axis_name))
            over = st.launch_steps >= cfg.superstep_budget
            st = st._replace(global_live=~(drained | stuck | over))
            return st, inbox, i + jnp.int32(1)

        st, inbox, i = jax.lax.while_loop(
            cond, body, (st, _load_mailbox(st), jnp.int32(0)))
        st = _tick_accounting(_store_mailbox(st, inbox), i, barrier)
        flags = TickFlags(
            steps=i, live=st.global_live,
            drained=jnp.all(jax.lax.all_gather(_drained(st), axis_name)))
        return st, flags

    return tick


def build_mesh_daemon(cfg: OcclConfig, t: StaticTables, axis_name: str,
                      rank_of_device: np.ndarray | None = None) -> Callable:
    """Per-device daemon body for use inside ``shard_map``: a launch is
    ``launch_prologue`` + one barrier tick (k = budget + 1 never binds —
    the in-body budget check flips ``global_live`` first)."""
    tick = build_mesh_tick(cfg, t, axis_name, rank_of_device, barrier=True)

    def daemon(st: DaemonState) -> DaemonState:
        st, _ = tick(launch_prologue(st),
                     jnp.int32(cfg.superstep_budget + 1))
        return st

    return daemon


def build_shardmap_tick(cfg: OcclConfig, t: StaticTables, mesh,
                        axis_name: str = "rank",
                        rank_of_device: np.ndarray | None = None,
                        barrier: bool = False) -> Callable:
    """Traceable ``tick(state, k) -> (state, TickFlags)`` over a real
    device mesh: state leaves are [R, ...] sharded along ``axis_name``,
    ``k`` and the returned flags are replicated.  NOT jitted — compose it
    inside a jitted step or wrap in ``jax.jit`` for host use."""
    from jax.sharding import PartitionSpec as P

    mesh_tick = build_mesh_tick(cfg, t, axis_name, rank_of_device,
                                barrier=barrier)

    def per_dev(st_slice: DaemonState, k):
        st1 = jax.tree_util.tree_map(lambda a: a[0], st_slice)
        st1, flags = mesh_tick(st1, k)
        return jax.tree_util.tree_map(lambda a: a[None], st1), flags

    return jax.shard_map(per_dev, mesh=mesh, in_specs=(P(axis_name), P()),
                         out_specs=(P(axis_name), P()), check_vma=False)
