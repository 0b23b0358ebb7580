"""Per-rank daemon superstep: the core of the DFCE-framework (paper Sec. 3.1).

One superstep, per rank:
  A. apply arriving connector messages (slice-burst commits + credit counts);
  B. maybe fetch one SQE (order policy controls eagerness, Sec. 3.2);
  C. all lanes at once: select each lane's current collective (two-phase
     blocking), gate a *burst* of up to ``cfg.burst_slices`` slice moves of
     its current primitive on connector credit, execute or spin/preempt
     (spin thresholds + stickiness, Sec. 3.2);
  D. bookkeeping for voluntary quit (Sec. 3.1.3).

Vectorized/burst execution (perf tentpole)
------------------------------------------
Phase C is *batched across lanes*: selection, gating and context advance are
[L, ...] array ops instead of a sequential Python loop of per-lane steps.
This is semantically faithful because eligibility is lane-partitioned
(``shared.lane[c] == lane``), so concurrent lanes never touch the same
collective's counters; the only shared sinks — the output heap, the CQ ring
and the scalar work counters — are combined with masked scatters
(``mode='drop'``) and cumulative-sum slot assignment.  The per-superstep
cost drops from L serialized full-heap ``dynamic_update_slice`` +
``lax.select`` copies (O(L * H)) to one [L, B * SLICE] windowed scatter, and
the O(C^2) queue-position comparison matrix is replaced by one batched
stable double-argsort shared by all lanes (O(L * C log C)).

A *burst* moves up to B contiguous slices of the lane's current primitive in
one superstep, where B = ``cfg.burst_slices``.  The burst is gated by
:func:`repro.core.primitives.burst_quota`: it never crosses a primitive-step
boundary and never exceeds the connector credit visible in the lagging
``head/tail`` mirrors, which now admit *counts* rather than booleans.  Why
deadlock freedom survives bursts: every slice of a burst is individually
credit-accounted, so the ring-capacity invariant from ``derive_slicing`` —
``sum(sent - consumed) <= R * (K - 1)`` around any communicator ring — still
guarantees an edge with both data and capacity; and a collective remains
preemptible *between* bursts (spin thresholds are evaluated every superstep,
B only bounds the atomic quantum, which is itself bounded by the per-round
slice cap K - 1).  With B = 1 the schedule is exactly the seed single-slice
semantics.

Sizing note: sustained burst throughput needs the connector depth to cover
the burst bandwidth-delay product — credits complete a ~3-superstep round
trip (commit, consume, credit-return), so K should be >= ~3B.  With a
shallower connector the ring saturates (in-flight == K) and relaxes into
the 1-slice/superstep credit-return equilibrium: still correct and
deadlock-free, just no faster than B = 1 (benchmarks/bench_collectives.py
uses conn_depth=32 for the B in {1, 4, 8} sweep; ``cfg.auto_conn_depth``
derives the bound automatically, and the runtime warns at registration
time when it is not met).

Launch-epoch clock + burst-aware stall accounting
-------------------------------------------------
Scheduling decisions are measured against the PER-LAUNCH clock
``st.launch_steps`` (zeroed in the daemon prologue), never the cumulative
``st.supersteps`` epoch clock:

* **Queue age.**  :func:`rebase_arrivals` (called from the prologue)
  compresses every active collective's ``arrival`` to its queue rank, a
  value < C; fetches and rotations during the launch stamp
  ``C + launch_steps``.  Arrival keys are therefore bounded by
  ``C + superstep_budget + 2`` per launch — validated in config to sit
  below ``QUEUE_KEY_DEMAND_STRIDE`` so the demand bonus and the PRIORITY
  class stride (``QUEUE_KEY_PRIO_STRIDE``) cannot bleed into the FIFO age
  no matter how many cumulative supersteps the runtime has executed.

* **Stall units.**  On a zero-progress superstep ``spin`` advances by the
  slices the credit gate DENIED (``min(B, room) - quota``, floored at 1),
  not by 1 per superstep; any partial grant still resets ``spin`` to 0
  (progress), exactly like the seed.  At B = 1 the two accountings are
  identical; at B > 1 a fully-stalled lane reaches its spin threshold up
  to B× sooner, so under contention the lane multiplexes between
  collectives at the same *slice* cadence it executes them, instead of
  wasting B-wide supersteps spinning.  The stall weight is QUEUE-LENGTH
  CONDITIONAL (``cfg.queue_conditional_stall``): a lane whose task queue
  holds no other eligible collective advances by 1 per stalled superstep
  instead — preempting a solo collective frees nothing, so B×-eager
  rotation during the ~3-superstep credit round trip would be pure churn
  (preempt-counter noise, boost resets).  Denied slices — including
  partial denials on supersteps that did move some slices — always
  accumulate unweighted in ``st.stall_slices`` (per collective) for
  Fig. 9-style observability.

Everything is branch-free fixed-shape array code so the loop compiles into
a single long-running XLA program — the daemon-kernel analogue.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import (
    QUEUE_KEY_DEMAND_STRIDE,
    QUEUE_KEY_PRIO_STRIDE,
    OcclConfig,
    OrderPolicy,
    ReduceOp,
)
from . import primitives as P
from .primitives import Prim
from .recorder import (
    EV_CHAIN_HANDOFF,
    EV_CQE,
    EV_PREEMPT,
    EV_STAGE_DONE,
    EV_SUBMIT,
    N_EVENT_KINDS,
)
from .state import DaemonState

# Queue-key stride between priority classes (per-launch arrival + demand
# bonus stay below this; see config.py for the class-separation proof).
_BIG = jnp.int32(QUEUE_KEY_PRIO_STRIDE)
_DEMAND = jnp.int32(QUEUE_KEY_DEMAND_STRIDE)

# Primitive action-flag lookups as device arrays (indexable by tracers).
PRIM_RECV = jnp.asarray(P.PRIM_RECV)
PRIM_SEND = jnp.asarray(P.PRIM_SEND)
PRIM_REDUCE = jnp.asarray(P.PRIM_REDUCE)
PRIM_COPY = jnp.asarray(P.PRIM_COPY)
PRIM_READS_IN = jnp.asarray(P.PRIM_READS_IN)


class SharedTables(NamedTuple):
    """Rank-independent static context (vmap in_axes=None)."""

    registered: jnp.ndarray   # [C] bool
    kind: jnp.ndarray         # [C]
    op: jnp.ndarray           # [C]
    lane: jnp.ndarray         # [C]
    lane_caps: jnp.ndarray    # [L] — per-lane slice burst cap (uniform
                              #   burst_slices unless the bandwidth-skew
                              #   model classifies the lane; <= B always,
                              #   so mailbox payload width is unchanged)
    n_steps: jnp.ndarray      # [C]
    n_slices: jnp.ndarray     # [C]
    n_rounds: jnp.ndarray     # [C]
    in_chunked: jnp.ndarray   # [C]
    out_chunked: jnp.ndarray  # [C]
    base_in_off: jnp.ndarray  # [C]
    base_out_off: jnp.ndarray # [C]
    # Composite-chain tables (tables.StaticTables; all-identity /
    # all-sentinel when no composite collectives are registered).
    next_coll: jnp.ndarray    # [C] — device-enqueued successor (-1 none)
    chain_tail: jnp.ndarray   # [C] — tail stage of c's chain (self: flat)
    chain_prio_inherit: jnp.ndarray  # [C] bool
    chain_mask: jnp.ndarray   # [C, C] bool — stages sharing c's chain
    chain_src: jnp.ndarray    # [C, M] — heap relink gather map (M == 0
                              #   when chain-free: the relink scatter is
                              #   not traced at all)
    chain_dst: jnp.ndarray    # [C, M]


class LocalTables(NamedTuple):
    """Per-rank static context (vmap in_axes=0)."""

    member: jnp.ndarray       # [C] bool
    prog_kind: jnp.ndarray    # [C, S]
    prog_chunk: jnp.ndarray   # [C, S]
    # Per-rank composite-chain maps (tables._build_rank_chain_maps): a
    # chain stage may cover only a subset of the logical members, so each
    # rank advances to ITS next participating stage and completes
    # logically at ITS last one.  Equal to the shared next_coll /
    # chain_tail rows for full-membership chains; -1 / self for flat.
    chain_next: jnp.ndarray   # [C] — rank's successor stage (-1 = tail)
    chain_tail_r: jnp.ndarray # [C] — rank's chain tail (self for flat)


class Mailbox(NamedTuple):
    """Per-lane connector traffic for one superstep (fwd burst + rev credit).

    ``fwd_count`` / ``rev_count`` are slice/credit *counts* (0..B), not
    validity bools: one superstep may commit a whole burst.
    """

    fwd_count: jnp.ndarray    # [L] i32 — slices committed this superstep
    fwd_coll: jnp.ndarray     # [L] i32
    fwd_payload: jnp.ndarray  # [L, B, SLICE]
    rev_count: jnp.ndarray    # [L] i32 — credits returned this superstep
    rev_coll: jnp.ndarray     # [L] i32


def _combine_by_op(op: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray
                   ) -> jnp.ndarray:
    """Per-lane reduction select: ``op`` is [L], a/b are [L, ...].

    A where-chain over the four ReduceOps is bit-identical to the seed's
    per-lane ``lax.switch`` (same elementwise ops, same operand order).
    """
    opc = jnp.clip(op, 0, 3).reshape(op.shape + (1,) * (a.ndim - 1))
    return jnp.where(
        opc == 0, a + b,
        jnp.where(opc == 1, jnp.maximum(a, b),
                  jnp.where(opc == 2, jnp.minimum(a, b), a * b)))


def _effective_prio(cfg, st):
    """Live priority including the bounded queue-age bump ([C]).

    With ``cfg.prio_aging_quantum`` set, a queued collective earns
    ``min(age // quantum, cap)`` extra priority where age is its launch-
    clock queue residency (``max_colls + launch_steps - arrival``) — the
    QoS starvation bound: a low class overtakes the class above it after
    a config-bounded wait, but the cap (<= one class stride in
    serving/qos.py) keeps it below the top class.  Clipped to the same
    +/-512 band as user priority so the queue-key magnitude proof in
    config.py is unchanged.  Quantum 0 returns ``st.prio`` untouched —
    bit-identical to the pre-aging scheduler.
    """
    if cfg.prio_aging_quantum <= 0:
        return st.prio
    age = jnp.maximum(
        jnp.int32(cfg.max_colls) + st.launch_steps - st.arrival, 0)
    bump = jnp.minimum(age // jnp.int32(cfg.prio_aging_quantum),
                       jnp.int32(cfg.prio_aging_cap))
    return jnp.clip(st.prio + bump, -512, 512)


def _lane_keys(cfg, st, shared, local):
    """Ascending queue-order key per collective for every lane at once.

    Returns (eligible [L, C], key [L, C]); front of lane l's queue is
    ``argmin(key[l])`` (ties broken by lowest collective id, matching the
    seed's comparison-matrix tie-break).
    """
    L = cfg.max_comms
    lanes = jnp.arange(L, dtype=jnp.int32)
    eligible = (st.tq_active & local.member)[None, :] \
        & (shared.lane[None, :] == lanes[:, None])
    key = jnp.broadcast_to(st.arrival[None, :], eligible.shape)
    if cfg.demand_steering:
        # Data already waiting in the recv connector => ring peers are on
        # this collective; steering toward it is the fastest decentralized
        # gang-convergence signal available (beyond-paper policy).
        demand = (st.tail < st.head_mirror).astype(jnp.int32)
        key = key - demand[None, :] * _DEMAND
    if cfg.order_policy == OrderPolicy.PRIORITY:
        # Higher priority first; FIFO (+demand) within equal priority.
        # Aging (if configured) bumps the effective class of long-queued
        # collectives — the serving QoS starvation bound.
        key = (-_effective_prio(cfg, st)[None, :]) * _BIG + key
    key = jnp.where(eligible, key, jnp.iinfo(jnp.int32).max)
    return eligible, key


def _lane_positions(key):
    """Task-queue position per (lane, collective) — batched stable ranks.

    ``argsort(argsort(key))`` along the collective axis yields each entry's
    rank in ascending key order with ties broken by index (jnp.argsort is
    stable), replacing the seed's O(C^2) pairwise comparison matrix.
    """
    order = jnp.argsort(key, axis=1)
    return jnp.argsort(order, axis=1).astype(jnp.int32)


def _thresholds(cfg, st, pos):
    """Effective spin thresholds (stickiness scheme, Sec. 3.2); [L, C]."""
    if cfg.stickiness:
        base = cfg.spin_base - pos * cfg.spin_decr + st.boost[None, :]
    else:
        base = jnp.full_like(pos, cfg.spin_base)
    return jnp.clip(base, cfg.spin_min, cfg.spin_max)


def rebase_arrivals(st: DaemonState) -> DaemonState:
    """Launch prologue: re-express queue age on the fresh launch clock.

    Active collectives keep their relative order but their ``arrival``
    values are compressed to queue ranks (< C, ties broken by lowest
    collective id exactly like the key argmin); inactive slots reset to 0.
    New fetches/rotations during the launch stamp ``C + launch_steps``, so
    carryover work always sorts ahead of work that arrives later — the
    same order the unbounded epoch clock produced, now bounded per launch.

    Operates on the last axis, so it works on both the per-rank [C] state
    (mesh backend) and the batched [R, C] state (sim backend).
    """
    key = jnp.where(st.tq_active, st.arrival, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key, axis=-1)
    ranks = jnp.argsort(order, axis=-1).astype(jnp.int32)
    return st._replace(arrival=jnp.where(st.tq_active, ranks, 0))


@functools.lru_cache(maxsize=None)
def _burst_offsets(L: int, B: int) -> np.ndarray:
    """Precomputed [L, B] burst-offset table for the inbox scatter (the
    static part of the row/slot index grid; a cached HOST constant — a
    device array built here would be a tracer inside the daemon trace)."""
    return np.ascontiguousarray(
        np.broadcast_to(np.arange(B, dtype=np.int32)[None, :], (L, B)))


def apply_inbox(cfg: OcclConfig, st: DaemonState, inbox: Mailbox
                ) -> DaemonState:
    """Phase A: commit arriving slice bursts into the recv-connector mirror
    and arriving credit counts into the send-side tail mirror — one batched
    scatter over all lanes.

    With ``cfg.vectorized_inbox`` the (coll, slot) scatter grid is
    flattened through the precomputed [L, B] burst-offset table into ONE
    single-axis scatter over the [C*K, SLICE] payload view (the inbox
    analogue of the heap-window trick: one index dimension instead of a
    two-axis scatter; masked entries route to the dropped row C*K).  The
    written slots and values are identical either way — bit-identical
    results, guarded by the fast-path equivalence tests.
    """
    K, B, C = cfg.conn_depth, cfg.burst_slices, cfg.max_colls
    L = cfg.max_comms
    bidx = _burst_offsets(L, B)                             # [L, B]

    c = jnp.clip(inbox.fwd_coll, 0, C - 1)                  # [L]
    cnt = jnp.clip(inbox.fwd_count, 0, B)                   # [L]
    take = bidx < cnt[:, None]                              # [L, B]
    slot = (st.head_mirror[c][:, None] + bidx) % K
    vals = inbox.fwd_payload.astype(st.payload.dtype)
    # Lanes are coll-disjoint (a collective is bound to one lane); masked
    # entries are routed to a dropped target.
    if cfg.vectorized_inbox:
        flat = jnp.where(take, c[:, None] * K + slot, C * K)
        payload = (st.payload.reshape(C * K, -1)
                   .at[flat].set(vals, mode="drop")
                   .reshape(st.payload.shape))
    else:
        row = jnp.where(take, c[:, None], C)
        payload = st.payload.at[row, slot].set(vals, mode="drop")
    head_mirror = st.head_mirror.at[c].add(cnt)

    rc = jnp.clip(inbox.rev_coll, 0, C - 1)
    tail_mirror = st.tail_mirror.at[rc].add(
        jnp.maximum(inbox.rev_count, 0))
    return st._replace(
        head_mirror=head_mirror, tail_mirror=tail_mirror, payload=payload
    )


def _record_events(cfg: OcclConfig, st: DaemonState, kinds: jnp.ndarray,
                   colls: jnp.ndarray, valid: jnp.ndarray) -> DaemonState:
    """Append masked events to the rank's flight-recorder ring.

    Same masked-scatter ring-append pattern as the CQ ring (lanes_step):
    exclusive-cumsum slot assignment over the valid mask, invalid entries
    routed to a dropped target.  A batch larger than the ring would map
    two events onto one slot WITHIN a single scatter (nondeterministic
    winner), so all but the newest ``recorder_len`` events of the batch
    are pre-dropped — ring semantics ("keep the newest events") are
    unchanged and the write stays collision-free for any
    ``recorder_len >= 1``.  ``fr_step`` stamps the cumulative epoch
    clock; ``fr_kinds`` keeps wrap-proof per-kind cumulative counters
    (dropped events still count).  Compiled out entirely when
    ``cfg.flight_recorder`` is off.
    """
    if not cfg.flight_recorder:
        return st
    FR = cfg.recorder_len
    n = valid.astype(jnp.int32)
    off = jnp.cumsum(n) - n                                 # exclusive scan
    total = jnp.sum(n)
    keep = valid & (off >= total - FR)
    slot = (st.fr_count + off) % FR
    tgt = jnp.where(keep, slot, FR)
    ktgt = jnp.where(valid, kinds, N_EVENT_KINDS)
    return st._replace(
        fr_kind=st.fr_kind.at[tgt].set(kinds, mode="drop"),
        fr_coll=st.fr_coll.at[tgt].set(colls, mode="drop"),
        fr_step=st.fr_step.at[tgt].set(st.supersteps, mode="drop"),
        fr_count=st.fr_count + total,
        fr_kinds=st.fr_kinds.at[ktgt].add(1, mode="drop"),
    )


def fetch_sqe(cfg: OcclConfig, st: DaemonState, shared: SharedTables,
              local: LocalTables) -> tuple[DaemonState, jnp.ndarray]:
    """Phase B: pop at most one SQE into the task queue (paper Sec. 3.1.2).

    FIFO policy fetches lazily (queue empty or stuck); PRIORITY fetches
    eagerly every superstep (paper: "checking the SQ more frequently").
    """
    has_sqe = st.sq_read < st.sq_size
    if cfg.order_policy == OrderPolicy.PRIORITY:
        want = has_sqe
    else:
        stuck_or_empty = (~st.made_prog_prev) | (~jnp.any(st.tq_active))
        want = has_sqe & stuck_or_empty
    slot = jnp.clip(st.sq_read, 0, cfg.sq_len - 1)
    c = st.sq_coll[slot]
    # Head-of-line wait: a re-submission of an in-flight collective waits
    # (the runtime never has two executions of one collective concurrently).
    # For a composite chain the head's inflight bit covers the WHOLE chain
    # (set below via chain_mask, cleared when the tail completes), so a
    # re-submitted chain head also waits for its predecessor's device-
    # enqueued stages to drain.
    ok = want & (c >= 0) & ~st.inflight[c] & local.member[c] & shared.registered[c]
    qlen = jnp.sum(st.tq_active).astype(jnp.int32)
    one = jnp.where(ok, 1, 0)
    # Per-SQE out_off overrides resolve END-TO-END: the override (or the
    # tail's registered default) lands on THIS RANK'S chain tail — its
    # logical output endpoint — while a chained head keeps its registered
    # intermediate output region.  Flat collectives have tail == c, so
    # the second write is a no-op and the behavior is exactly the seed's.
    # On a partial-membership chain a rank whose own tail is NOT the
    # logical tail (e.g. tree-reduce non-leaders) ignores the override:
    # it was sized for the logical endpoint's span, and this rank's
    # output is not part of the logical result.
    tail = local.chain_tail_r[c]
    use_ovr = (st.sq_out[slot] >= 0) & (tail == shared.chain_tail[c])
    resolved_out = jnp.where(use_ovr, st.sq_out[slot],
                             shared.base_out_off[tail])
    out_off = st.out_off.at[tail].set(
        jnp.where(ok, resolved_out, st.out_off[tail]))
    out_off = out_off.at[c].set(
        jnp.where(ok & (tail != c), shared.base_out_off[c], out_off[c]))
    st = st._replace(
        tq_active=st.tq_active.at[c].set(jnp.where(ok, True, st.tq_active[c])),
        inflight=st.inflight | (shared.chain_mask[c] & ok),
        # Launch-clock queue age: behind every rebased carryover (< C).
        arrival=st.arrival.at[c].set(
            jnp.where(ok, cfg.max_colls + st.launch_steps, st.arrival[c])),
        prio=st.prio.at[c].set(jnp.where(
            ok, jnp.clip(st.sq_prio[slot], -512, 512), st.prio[c])),
        in_off=st.in_off.at[c].set(jnp.where(
            ok,
            jnp.where(st.sq_in[slot] >= 0, st.sq_in[slot], shared.base_in_off[c]),
            st.in_off[c])),
        out_off=out_off,
        ctx_step=st.ctx_step.at[c].set(jnp.where(ok, 0, st.ctx_step[c])),
        ctx_slice=st.ctx_slice.at[c].set(jnp.where(ok, 0, st.ctx_slice[c])),
        ctx_round=st.ctx_round.at[c].set(jnp.where(ok, 0, st.ctx_round[c])),
        spin=st.spin.at[c].set(jnp.where(ok, 0, st.spin[c])),
        boost=st.boost.at[c].set(jnp.where(ok, 0, st.boost[c])),
        qlen_at_fetch=st.qlen_at_fetch.at[c].set(
            jnp.where(ok, qlen, st.qlen_at_fetch[c])),
        # Ready-to-complete clock: stamp queue entry on the cumulative
        # supersteps clock (monotonic across launches, so a collective
        # carried over a relaunch keeps accruing latency).
        fetch_step=st.fetch_step.at[c].set(
            jnp.where(ok, st.supersteps, st.fetch_step[c])),
        sq_read=st.sq_read + one,
    )
    st = _record_events(
        cfg, st,
        kinds=jnp.full((1,), EV_SUBMIT, jnp.int32),
        colls=jnp.reshape(c, (1,)),
        valid=jnp.reshape(ok, (1,)))
    return st, ok


def lanes_step(cfg: OcclConfig, st: DaemonState, shared: SharedTables,
               local: LocalTables, cond_relink: bool = False,
               defer_relink: bool = False
               ) -> tuple[DaemonState, jnp.ndarray, Mailbox]:
    """Phase C for ALL lanes: two-phase-blocking selection + one credit-gated
    slice burst per lane, fully vectorized over the lane axis.

    ``cond_relink`` wraps the chain-relink scatter in a ``lax.cond`` on
    "any chained stage completed this superstep" (mesh backend; each
    device's predicate is a scalar, so the branch is real and chain-free
    supersteps skip the gather entirely).

    ``defer_relink`` skips the in-step relink altogether: the caller is
    responsible for applying it after the step from the
    ``stage_completions`` delta (sim backend — under vmap the per-rank
    cond predicate is batched and would lower to a select that executes
    the O(M)-element gather EVERY superstep; the sim driver instead
    reduces the predicate over ranks outside the vmap, where the cond
    stays a real branch).

    Returns (state, moved_any, outbox).
    """
    K, SL, B = cfg.conn_depth, cfg.slice_elems, cfg.burst_slices
    C, L = cfg.max_colls, cfg.max_comms
    lanes = jnp.arange(L, dtype=jnp.int32)
    bidx = jnp.arange(B, dtype=jnp.int32)

    eligible, key = _lane_keys(cfg, st, shared, local)
    pos = _lane_positions(key)
    thr = _thresholds(cfg, st, pos)

    cur = st.cur                                            # [L]
    cur_c = jnp.clip(cur, 0, C - 1)
    cur_ok = (cur >= 0) & eligible[lanes, cur_c]
    overspun = cur_ok & (st.spin[cur_c] > thr[lanes, cur_c])
    if cfg.priority_preempts:
        # Same effective priority (aging included) as the queue key, so
        # an aged-up collective both sorts ahead AND preempts — one
        # consistent class ladder.
        ep = _effective_prio(cfg, st)
        higher = jnp.any(
            eligible & (ep[None, :] > ep[cur_c][:, None]), axis=1)
        overspun = overspun | (cur_ok & higher)

    # Preempt: context switch — dynamic context stays in the context buffer
    # (it already lives in ctx_* arrays: the lazy-saving optimization of
    # Sec. 4 is structural here), rotate to the back of the queue.  Overspun
    # lanes own disjoint collectives, so the scatter-add mask is exact.
    rot = jnp.zeros((C,), jnp.int32).at[cur_c].add(
        overspun.astype(jnp.int32)) > 0
    st = st._replace(
        preempts=st.preempts + rot.astype(st.preempts.dtype),
        arrival=jnp.where(rot, cfg.max_colls + st.launch_steps + 1,
                          st.arrival),
        spin=jnp.where(rot, 0, st.spin),
        boost=jnp.where(rot, 0, st.boost),
    )
    keep = cur_ok & ~overspun

    # Queue front after a possible rotation (only `arrival` changed).
    eligible, key = _lane_keys(cfg, st, shared, local)
    front = jnp.argmin(key, axis=1).astype(jnp.int32)       # [L]
    any_eligible = jnp.any(eligible, axis=1)
    cand = jnp.where(keep, cur, jnp.where(any_eligible, front, -1))
    c = jnp.clip(cand, 0, C - 1)                            # [L]
    valid = cand >= 0
    # Valid lanes select distinct collectives (lane-partitioned
    # eligibility); invalid lanes are routed to dropped scatter targets.
    cv = jnp.where(valid, c, C)                             # valid-gated tgt

    # --- gate a slice burst of the current primitive ---------------------
    step = jnp.clip(st.ctx_step[c], 0, local.prog_kind.shape[1] - 1)
    prim = local.prog_kind[c, step]                         # [L]
    chunk = local.prog_chunk[c, step]
    sl = st.ctx_slice[c]
    needs_recv = PRIM_RECV[prim] > 0
    needs_send = PRIM_SEND[prim] > 0
    does_reduce = PRIM_REDUCE[prim] > 0
    does_copy = PRIM_COPY[prim] > 0
    reads_in = PRIM_READS_IN[prim] > 0

    nsl = shared.n_slices[c]
    recv_avail = st.head_mirror[c] - st.tail[c]
    send_free = K - (st.head[c] - st.tail_mirror[c])
    # Per-lane burst width: the uniform cfg.burst_slices unless the
    # bandwidth-skew model capped this lane's class (lane_caps <= B, so
    # mailbox geometry is untouched; with the model off this is a [L]
    # array of B and every value below matches the scalar-B math).
    Bl = shared.lane_caps                                   # [L]
    quota = P.burst_quota(Bl, nsl - sl, recv_avail, send_free,
                          needs_recv, needs_send)
    gate = valid & (prim != Prim.NULL) & (quota > 0)
    n = jnp.where(gate, quota, 0)                           # [L] burst size
    # Burst-aware stall accounting: the slices this lane WANTED (a full
    # burst, capped by the primitive step) minus the slices the credit
    # gate granted, floored at one so a stalled B = 1 superstep advances
    # spin by exactly 1 — bit-identical to the seed superstep counting.
    want = jnp.minimum(Bl, jnp.maximum(nsl - sl, 1))
    denied = jnp.maximum(want - n, 1)                       # [L] denied
    # Queue-length-conditional stall weight: preempting a SOLO collective
    # (no other eligible collective queued on its lane) frees nothing, so
    # a lane briefly blocked on the burst credit round trip should not
    # reach its spin threshold B× sooner — it advances by 1 per stalled
    # superstep (the seed cadence).  Contended lanes keep the fast
    # B-scaled denied-slice accounting that closed the PR-2 contention
    # gap.  ``eligible`` includes the current collective, so solo means
    # queue length <= 1.
    if cfg.queue_conditional_stall:
        solo = jnp.sum(eligible, axis=1) <= 1               # [L]
        stalled = jnp.where(solo, 1, denied)
    else:
        stalled = denied

    # --- execute the fused actions on the burst (paper Fig. 3) -----------
    slots = (st.tail[c][:, None] + bidx[None, :]) % K       # [L, B] ring read
    recv_val = st.payload[c[:, None], slots]                # [L, B, SL]
    rnd = st.ctx_round[c]
    chunk_stride = shared.n_rounds[c] * nsl * SL   # padded chunk extent
    within = (rnd * nsl + sl) * SL                 # (round, slice) offset
    in_base = (st.in_off[c]
               + jnp.where(shared.in_chunked[c] > 0, chunk, 0) * chunk_stride
               + within)
    out_base = (st.out_off[c]
                + jnp.where(shared.out_chunked[c] > 0, chunk, 0) * chunk_stride
                + within)
    # Per-lane contiguous [B*SL] windows (bursts never straddle a step
    # boundary, so the slice range is contiguous in the heap).  L is a
    # small static constant; dynamic_slice stays a memcpy where a batched
    # elementwise gather/scatter would serialize on CPU/TPU backends.
    span = jnp.arange(B * SL, dtype=jnp.int32)
    in_val = jnp.stack([
        jax.lax.dynamic_slice(st.heap_in, (in_base[l],), (B * SL,))
        for l in range(L)
    ]).reshape(L, B, SL)

    opv = shared.op[c]
    if cfg.use_pallas:
        from ..kernels import ops as kops
        flags = jnp.stack([
            needs_recv.astype(jnp.int32), does_reduce.astype(jnp.int32),
            reads_in.astype(jnp.int32), opv.astype(jnp.int32),
        ], axis=1)                                          # [L, 4]
        flags_lb = jnp.broadcast_to(
            flags[:, None, :], (L, B, 4)).reshape(L * B, 4)
        value = kops.fused_primitive_batch(
            recv_val.reshape(L * B, SL), in_val.reshape(L * B, SL),
            flags_lb, interpret=cfg.pallas_interpret).reshape(L, B, SL)
    else:
        reduced = _combine_by_op(opv, recv_val, in_val)
        sel = lambda m: m[:, None, None]
        value = jnp.where(
            sel(does_reduce), reduced,
            jnp.where(sel(needs_recv), recv_val,
                      jnp.where(sel(reads_in), in_val,
                                jnp.zeros_like(in_val))))

    # Per-lane [B*SL] read-modify-write windows replace the seed's L
    # serialized full-heap dynamic_update_slice + lax.select copies
    # (O(L * B * SLICE) moved instead of O(L * H)).  The heap carries
    # B*SLICE scratch padding (state.init_state) so windows at the top of
    # the allocated region never clamp-shift.
    write_out = gate & does_copy
    out_limit = jnp.where(write_out, n, 0) * SL             # elems to write
    vals = value.reshape(L, B * SL).astype(st.heap_out.dtype)
    heap_out = st.heap_out
    for l in range(L):
        window = jax.lax.dynamic_slice(heap_out, (out_base[l],), (B * SL,))
        blend = jnp.where(span < out_limit[l], vals[l], window)
        heap_out = jax.lax.dynamic_update_slice(heap_out, blend,
                                                (out_base[l],))

    n_recv = jnp.where(gate & needs_recv, n, 0)
    n_send = jnp.where(gate & needs_send, n, 0)

    # --- advance the dynamic context (round, primitive, slice) -----------
    new_slice = sl + n
    step_done = gate & (new_slice >= nsl)
    seq_done = step_done & (st.ctx_step[c] + 1 >= shared.n_steps[c])
    next_step = jnp.where(
        seq_done, 0,
        jnp.where(step_done, st.ctx_step[c] + 1, st.ctx_step[c]))
    next_slice = jnp.where(step_done, 0, new_slice)
    next_round = jnp.where(seq_done, rnd + 1, rnd)
    coll_done = seq_done & (next_round >= shared.n_rounds[c])

    cg = jnp.where(gate, c, C)                              # gate-gated tgt
    st = st._replace(
        heap_out=heap_out,
        tail=st.tail.at[c].add(n_recv),
        head=st.head.at[c].add(n_send),
        ctx_step=st.ctx_step.at[cg].set(next_step, mode="drop"),
        ctx_slice=st.ctx_slice.at[cg].set(next_slice, mode="drop"),
        ctx_round=st.ctx_round.at[cg].set(next_round, mode="drop"),
        spin=st.spin.at[cv].set(
            jnp.where(gate, 0, st.spin[c] + stalled), mode="drop"),
        # The observability counter always records DENIED SLICES (partial
        # denials included), independent of the queue-conditional spin
        # weight: a persistently credit-starved lane shows its true
        # starvation even when solo patience keeps it from preempting.
        stall_slices=st.stall_slices.at[cv].add(
            jnp.where(gate, jnp.maximum(want - n, 0), denied),
            mode="drop"),
        # Stickiness: a successful primitive boosts its successors' spin
        # thresholds (gang-convergence pressure, Sec. 3.2).
        boost=st.boost.at[c].add(
            jnp.where(step_done & ~coll_done & jnp.bool_(cfg.stickiness),
                      cfg.spin_boost, 0)),
        slices_moved=st.slices_moved + jnp.sum(n),
    )

    # --- completion + chain advance (Sec. 3.1.2 / composite layer) --------
    # A completing stage with a registered successor (tables.next_coll)
    # enqueues the successor SQE ON DEVICE in the same superstep: the
    # whole chain advances inside one launch with no host round trip per
    # stage.  Only LOGICAL completions (chain tails and flat collectives)
    # write a CQE / advance `completed` — the host sees one completion
    # per submitted logical collective; per-stage progress is tracked
    # separately in `stage_completions`.  With no chains registered,
    # next_coll is all -1, chain_mask is the identity and every branch
    # below reduces bit-exactly to the seed completion semantics.
    #
    # The CQ is a RING: slots wrap modulo cq_len so completions past cq_len
    # per launch rotate through the buffer instead of silently overwriting
    # the last CQE (host reconciliation counts completions exactly via the
    # cumulative `completed` matrix, sqcq.HostQueues.reconcile).
    # Successors are PER RANK (local.chain_next): on a partial-membership
    # chain a rank advances to its own next participating stage (skipping
    # stages it is not a member of) and completes logically at its own
    # tail.  For full-membership chains chain_next == next_coll row-wise
    # and this is exactly the global-successor semantics.
    succ = local.chain_next[c]                              # [L]
    succ_c = jnp.clip(succ, 0, C - 1)
    chain_adv = coll_done & (succ >= 0)                     # enqueue next
    logical_done = coll_done & (succ < 0)                   # tail or flat
    done_i = logical_done.astype(jnp.int32)
    slot_off = jnp.cumsum(done_i) - done_i                  # exclusive scan
    cq_slot = (st.cq_count + slot_off) % cfg.cq_len
    cq_tgt = jnp.where(logical_done, cq_slot, cfg.cq_len)
    cd = jnp.where(coll_done, c, C)
    # Inflight clears CHAIN-WIDE at logical completion (set chain-wide at
    # head fetch), so a re-submitted head waits for the full chain.
    clear = jnp.any(shared.chain_mask[c] & logical_done[:, None], axis=0)
    # Successor context: fresh dynamic context, inherited priority (when
    # the chain's inherit flag is set), arrival stamped on the launch
    # clock like any rotation — the successor joins the BACK of its
    # lane's queue and competes under the normal preemption rules.
    sc = jnp.where(chain_adv, succ_c, C)                    # drop-gated tgt
    succ_prio = jnp.where(shared.chain_prio_inherit[succ_c],
                          st.prio[c], 0)
    # Intermediate successors run at their registered output region; the
    # rank's TAIL successor keeps the out_off pre-resolved at head fetch
    # (the per-SQE override's logical endpoint).
    sc_mid = jnp.where(chain_adv & (local.chain_next[succ_c] >= 0),
                       succ_c, C)
    st = st._replace(
        tq_active=st.tq_active.at[cd].set(False, mode="drop")
                             .at[sc].set(True, mode="drop"),
        inflight=st.inflight & ~clear,
        completed=st.completed.at[c].add(done_i),
        stage_completions=st.stage_completions.at[c].add(
            coll_done.astype(jnp.int32)),
        # Ready-to-complete latency on the cumulative supersteps clock:
        # each completing stage accrues (now - queue-entry stamp); the
        # event counter reconciles against stage_completions (every
        # completion is latency-accounted exactly once).  Device-enqueued
        # chain successors are stamped at THIS superstep — their wait
        # starts when the predecessor hands off, not at host submit.
        rtc_latency=st.rtc_latency.at[cd].add(
            st.supersteps - st.fetch_step[c], mode="drop"),
        rtc_events=st.rtc_events.at[cd].add(1, mode="drop"),
        fetch_step=st.fetch_step.at[sc].set(st.supersteps, mode="drop"),
        arrival=st.arrival.at[sc].set(
            cfg.max_colls + st.launch_steps + 1, mode="drop"),
        prio=st.prio.at[sc].set(succ_prio, mode="drop"),
        ctx_step=st.ctx_step.at[sc].set(0, mode="drop"),
        ctx_slice=st.ctx_slice.at[sc].set(0, mode="drop"),
        ctx_round=st.ctx_round.at[sc].set(0, mode="drop"),
        spin=st.spin.at[sc].set(0, mode="drop"),
        boost=st.boost.at[sc].set(0, mode="drop"),
        in_off=st.in_off.at[sc].set(shared.base_in_off[succ_c],
                                    mode="drop"),
        out_off=st.out_off.at[sc_mid].set(shared.base_out_off[succ_c],
                                          mode="drop"),
        cq_coll=st.cq_coll.at[cq_tgt].set(c, mode="drop"),
        cq_count=st.cq_count + jnp.sum(done_i),
        cur=jnp.where(coll_done | ~valid, -1, cand),
    )

    # Flight recorder: one batched ring append for this superstep's
    # transitions — preemptions (pre-rotation lane owner), stage
    # completions, on-device chain hand-offs and host-visible CQEs.
    if cfg.flight_recorder:
        st = _record_events(
            cfg, st,
            kinds=jnp.concatenate([
                jnp.full((L,), EV_PREEMPT, jnp.int32),
                jnp.full((L,), EV_STAGE_DONE, jnp.int32),
                jnp.full((L,), EV_CHAIN_HANDOFF, jnp.int32),
                jnp.full((L,), EV_CQE, jnp.int32),
            ]),
            colls=jnp.concatenate([cur_c, c, c, c]),
            valid=jnp.concatenate(
                [overspun, coll_done, chain_adv, logical_done]))

    # Chain hand-off relink: rewrite the successor's padded input span in
    # heap_in from the predecessor's just-finalized heap_out region via
    # the registration-time composed stage maps (pads zero-filled).  The
    # gather/scatter pair is only TRACED when the registration actually
    # contains chains (M > 0) — chain-free daemons pay nothing.  The
    # relink map of row c describes the GLOBAL edge c -> next_coll[c], so
    # it fires only when this rank's successor IS that stage: a rank
    # skipping intermediate stages (partial membership) has nothing to
    # hand off — its skipped successor's input is produced elsewhere or
    # never read (broadcast non-roots).
    if shared.chain_src.shape[1] > 0 and not defer_relink:
        relink_adv = chain_adv & (succ == shared.next_coll[c])
        heap_out = st.heap_out

        def _relink(heap_in):
            src = shared.chain_src[c]                       # [L, M]
            vals = jnp.where(src >= 0, heap_out[jnp.maximum(src, 0)],
                             0).astype(heap_in.dtype)
            dstg = jnp.where(relink_adv[:, None], shared.chain_dst[c],
                             jnp.int32(1 << 30))
            return heap_in.at[dstg].set(vals, mode="drop")

        if cond_relink:
            # Mesh backend: supersteps that complete no chained stage
            # skip the relink gather/scatter entirely (a real branch on
            # a device; under vmap this would degenerate to a select).
            heap_in = jax.lax.cond(jnp.any(relink_adv), _relink,
                                   lambda h: h, st.heap_in)
        else:
            heap_in = _relink(st.heap_in)
        st = st._replace(heap_in=heap_in)

    outbox = Mailbox(
        fwd_count=n_send,
        fwd_coll=c,
        fwd_payload=value.astype(st.payload.dtype),
        rev_count=n_recv,
        rev_coll=c,
    )
    return st, jnp.any(gate), outbox


def chain_relink_fired(shared: SharedTables, local: LocalTables,
                       prev_stage_completions: jnp.ndarray,
                       stage_completions: jnp.ndarray) -> jnp.ndarray:
    """[C] mask of chained stages whose hand-off relink must fire on this
    rank this superstep, recovered from the ``stage_completions`` delta.

    Matches the in-step ``relink_adv`` gating of :func:`lanes_step`: the
    stage completed here this superstep AND this rank's chain successor is
    the stage's GLOBAL next stage (a partial-membership rank that skips the
    successor has nothing to hand off — its skipped successor's input is
    produced elsewhere or never read)."""
    return ((stage_completions > prev_stage_completions)
            & (local.chain_next == shared.next_coll)
            & (shared.next_coll >= 0))


def rank_superstep(cfg: OcclConfig, shared: SharedTables, local: LocalTables,
                   st: DaemonState, inbox: Mailbox,
                   cond_relink: bool = False, defer_relink: bool = False
                   ) -> tuple[DaemonState, Mailbox]:
    """One full superstep for one rank."""
    st = apply_inbox(cfg, st, inbox)
    st, fetched = fetch_sqe(cfg, st, shared, local)
    st, moved_any, outbox = lanes_step(cfg, st, shared, local,
                                       cond_relink=cond_relink,
                                       defer_relink=defer_relink)

    progress = moved_any | fetched
    st = st._replace(
        supersteps=st.supersteps + 1,
        launch_steps=st.launch_steps + 1,
        no_prog=jnp.where(progress, 0, st.no_prog + 1),
        made_prog_prev=moved_any,
    )
    return st, outbox
