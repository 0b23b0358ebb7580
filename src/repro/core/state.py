"""Daemon state: dynamic contexts, connectors, task queue, SQ/CQ mirrors.

Every field is a fixed-shape array so the daemon compiles to one XLA program
(the analogue of the long-running daemon kernel, paper Sec. 3.1).  In the
sim backend each array carries a leading ``n_ranks`` axis and the superstep
is vmapped; in the mesh backend the same arrays are per-device inside
``shard_map``.

Connector representation (paper Fig. 3, Sec. 2.3): the connector between
ring-neighbors ``r -> next(r)`` is a lock-free ring buffer of ``K`` slice
slots.  The *writer* owns the committed-write counter ``head`` and a lagging
mirror of the reader's ``tail`` (credits); the *reader* owns ``tail``, a
lagging mirror of ``head`` and the payload slots.  Committed writes stay
visible to the peer even if the writing collective is preempted — the
visibility property that makes decentralized preemption safe.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from .config import OcclConfig
from .recorder import N_EVENT_KINDS


def heap_scratch_elems(cfg: OcclConfig) -> int:
    """Physical heap padding past the allocatable region: the scheduler's
    per-lane [B*SLICE] burst windows (read and read-modify-write) must
    never clamp-shift at the top of the heap.  Logical offsets handed out
    by the runtime — and every staging-engine index — stay < heap_elems;
    only the daemon's windowed slices may graze the scratch tail."""
    return cfg.burst_slices * cfg.slice_elems


class DaemonState(NamedTuple):
    # --- data heap (send/recv buffers; addresses = heap offsets) --------
    # heap_in is written exclusively through staging.StagingEngine (fused
    # index-map scatters; donated on accelerator backends), heap_out by
    # the daemon's burst windows and read back via the engine's fused
    # gather — no host-side heap mirrors anywhere on the bulk I/O path.
    heap_in: jnp.ndarray       # [H]
    heap_out: jnp.ndarray      # [H]

    # --- connectors (per collective; dedicated, paper Sec. 5.1) ---------
    head: jnp.ndarray          # [C] i32 — my committed writes (send side)
    tail_mirror: jnp.ndarray   # [C] i32 — reader's consumed count (lagging)
    head_mirror: jnp.ndarray   # [C] i32 — upstream's commits (lagging)
    tail: jnp.ndarray          # [C] i32 — my consumed count (recv side)
    payload: jnp.ndarray       # [C, K, SLICE] — recv-connector slots

    # --- task queue + dynamic contexts (paper Sec. 3.1.1) ---------------
    tq_active: jnp.ndarray     # [C] bool — in my task queue
    arrival: jnp.ndarray       # [C] i32 — queue-order key (FIFO / rotate)
    prio: jnp.ndarray          # [C] i32 — user priority (SQE)
    cur: jnp.ndarray           # [L] i32 — executing collective per lane (-1)
    ctx_step: jnp.ndarray      # [C] i32 — primitive index
    ctx_slice: jnp.ndarray     # [C] i32 — slice index inside the chunk
    ctx_round: jnp.ndarray     # [C] i32 — primitive-sequence repetition
    spin: jnp.ndarray          # [C] i32 — current primitive's spin count
    boost: jnp.ndarray        # [C] i32 — stickiness boost (success bonus)
    in_off: jnp.ndarray        # [C] i32 — live buffer addresses (SQE-set)
    out_off: jnp.ndarray       # [C] i32

    # --- SQ / CQ (paper Sec. 3.1.2) --------------------------------------
    sq_coll: jnp.ndarray       # [SQL] i32
    sq_prio: jnp.ndarray       # [SQL] i32
    sq_in: jnp.ndarray         # [SQL] i32 (-1 = keep registered default)
    sq_out: jnp.ndarray        # [SQL] i32
    sq_size: jnp.ndarray       # [] i32 — valid SQEs
    sq_read: jnp.ndarray       # [] i32 — daemon cursor
    cq_coll: jnp.ndarray       # [CQL] i32
    cq_count: jnp.ndarray      # [] i32
    inflight: jnp.ndarray      # [C] bool — submitted, not yet completed

    # --- in-flight connector messages (survive daemon relaunch) ---------
    # A credit/slice-burst emitted on the fabric's last superstep has not
    # been applied yet; dropping it would permanently wedge the connector
    # counters.  The mailbox is therefore part of the persistent state.
    # Counts (not bools): one message carries up to ``burst_slices`` slices.
    mb_fwd_count: jnp.ndarray   # [L] i32
    mb_fwd_coll: jnp.ndarray    # [L] i32
    mb_fwd_payload: jnp.ndarray # [L, B, SLICE]
    mb_rev_count: jnp.ndarray   # [L] i32
    mb_rev_coll: jnp.ndarray    # [L] i32

    # --- counters / lifecycle --------------------------------------------
    # Launch-epoch clock: ``supersteps`` is the cumulative epoch clock
    # (never reset; observability only), ``launch_steps`` is the per-launch
    # clock (zeroed in the daemon prologue) that the superstep budget and
    # the task-queue arrival keys are measured against, and ``epoch``
    # counts daemon launches.  Only the launch clock feeds scheduling
    # decisions, so no decision ever depends on how long the runtime has
    # been alive.
    completed: jnp.ndarray     # [C] i32 — LOGICAL completions (chain tails
                               #   and flat collectives; repeat submissions
                               #   accumulate) — drives host reconciliation
    stage_completions: jnp.ndarray  # [C] i32 — per-stage completions,
                               #   counting chain intermediates too (chain
                               #   observability; == completed when no
                               #   composite collectives are registered)
    preempts: jnp.ndarray      # [C] i32 — context switches (Fig. 9)
    stall_slices: jnp.ndarray  # [C] i32 — burst slices denied by credit
                               #   gating, counting partial denials (stall
                               #   accounting; spin advances by these units
                               #   on zero-progress supersteps)
    qlen_at_fetch: jnp.ndarray # [C] i32 — task-queue length at SQE fetch (Fig. 9)
    supersteps: jnp.ndarray    # [] i32 — cumulative epoch clock
    launch_steps: jnp.ndarray  # [] i32 — per-launch clock (budget domain)
    epoch: jnp.ndarray         # [] i32 — daemon launch counter
    no_prog: jnp.ndarray       # [] i32 — consecutive no-progress supersteps
    made_prog_prev: jnp.ndarray  # [] bool — lazy-fetch gate input
    slices_moved: jnp.ndarray  # [] i32 — work counter (bandwidth accounting)
    global_live: jnp.ndarray   # [] bool — fabric-wide continue flag

    # --- tick/overlap observability (compute-communication overlap) ------
    # ``tick()`` is the unit of daemon progress since the tickable-daemon
    # refactor: drive()'s launches and in-step overlap ticks both run the
    # same loop, tagged by a static barrier/overlap bit.  The invariant
    # ``overlap_steps + barrier_steps == supersteps`` holds because EVERY
    # superstep executes inside some tick.  Ready-to-complete latency is
    # measured on the cumulative ``supersteps`` clock: ``fetch_step[c]``
    # stamps when c entered the task queue (SQE fetch or device-enqueued
    # chain successor) and completion accumulates the delta into
    # ``rtc_latency``; ``rtc_events`` counts the completions accounted
    # (== stage_completions, asserted by tier-1 tests).
    fetch_step: jnp.ndarray    # [C] i32 — supersteps stamp at queue entry
    rtc_latency: jnp.ndarray   # [C] i32 — cumulative ready-to-complete
                               #   supersteps (sum over completions)
    rtc_events: jnp.ndarray    # [C] i32 — completions the latency counter
                               #   accounted (reconciles stage_completions)
    tick_calls: jnp.ndarray    # [] i32 — tick() invocations
    overlap_steps: jnp.ndarray # [] i32 — supersteps run by overlap ticks
                               #   (interleaved with compute in a step)
    barrier_steps: jnp.ndarray # [] i32 — supersteps run by barrier ticks
                               #   (drive()/drain: compute is blocked)

    # --- flight recorder (core/recorder.py; cfg.flight_recorder) ---------
    # Fixed-size per-rank ring of scheduling events stamped with the
    # cumulative epoch clock; ``fr_count`` is the total appended (ring
    # index = count % recorder_len) and ``fr_kinds`` keeps wrap-proof
    # per-kind cumulative counters that reconcile with the scheduler's
    # own counters (see recorder.py).  All i32 — they ride the f32
    # bitcast of device_api.encode_state unchanged.
    fr_kind: jnp.ndarray       # [FR] i32 — event kind (-1 = empty slot)
    fr_coll: jnp.ndarray       # [FR] i32 — stage/collective id
    fr_step: jnp.ndarray       # [FR] i32 — epoch-clock stamp
    fr_count: jnp.ndarray      # [] i32 — events appended (monotonic)
    fr_kinds: jnp.ndarray      # [N_EVENT_KINDS] i32 — cumulative per kind


def init_state(cfg: OcclConfig, per_rank: bool = True,
               sharding=None) -> DaemonState:
    """Fresh state; leading rank axis added when ``per_rank``.

    ``sharding`` (mesh backend) is a ``NamedSharding`` placing the leading
    rank axis on the mesh's rank axis: every [R, ...] leaf is created by
    a jit with that output sharding, so each device materializes only its
    own rank's rows — the state never passes through one device."""
    if sharding is not None:
        import jax

        assert per_rank, "a sharded state carries the leading rank axis"
        return jax.jit(lambda: init_state(cfg, per_rank=True),
                       out_shardings=sharding)()
    C, K, L = cfg.max_colls, cfg.conn_depth, cfg.max_comms
    B = cfg.burst_slices
    SQL, CQL, H, SL = cfg.sq_len, cfg.cq_len, cfg.heap_elems, cfg.slice_elems
    dt = jnp.dtype(cfg.dtype)

    def z(shape, dtype=jnp.int32, fill=0):
        a = jnp.full(shape, fill, dtype)
        return a

    pad = heap_scratch_elems(cfg)
    s = DaemonState(
        heap_in=z((H + pad,), dt),
        heap_out=z((H + pad,), dt),
        head=z((C,)), tail_mirror=z((C,)), head_mirror=z((C,)), tail=z((C,)),
        payload=z((C, K, SL), dt),
        tq_active=z((C,), jnp.bool_, False),
        arrival=z((C,)),
        prio=z((C,)),
        cur=z((L,), jnp.int32, -1),
        ctx_step=z((C,)), ctx_slice=z((C,)), ctx_round=z((C,)),
        spin=z((C,)), boost=z((C,)),
        in_off=z((C,)), out_off=z((C,)),
        sq_coll=z((SQL,), jnp.int32, -1), sq_prio=z((SQL,)),
        sq_in=z((SQL,), jnp.int32, -1), sq_out=z((SQL,), jnp.int32, -1),
        sq_size=z(()), sq_read=z(()),
        cq_coll=z((CQL,), jnp.int32, -1), cq_count=z(()),
        inflight=z((C,), jnp.bool_, False),
        mb_fwd_count=z((L,)),
        mb_fwd_coll=z((L,)),
        mb_fwd_payload=z((L, B, SL), dt),
        mb_rev_count=z((L,)),
        mb_rev_coll=z((L,)),
        completed=z((C,)), stage_completions=z((C,)),
        preempts=z((C,)), stall_slices=z((C,)),
        qlen_at_fetch=z((C,)),
        supersteps=z(()), launch_steps=z(()), epoch=z(()), no_prog=z(()),
        made_prog_prev=z((), jnp.bool_, False),
        slices_moved=z(()),
        global_live=z((), jnp.bool_, True),
        fetch_step=z((C,)), rtc_latency=z((C,)), rtc_events=z((C,)),
        tick_calls=z(()), overlap_steps=z(()), barrier_steps=z(()),
        fr_kind=z((cfg.recorder_len,), jnp.int32, -1),
        fr_coll=z((cfg.recorder_len,), jnp.int32, -1),
        fr_step=z((cfg.recorder_len,)),
        fr_count=z(()),
        fr_kinds=z((N_EVENT_KINDS,)),
    )
    if per_rank:
        s = s._replace(
            **{
                f: jnp.broadcast_to(v, (cfg.n_ranks,) + v.shape).copy()
                for f, v in s._asdict().items()
            }
        )
    return s
