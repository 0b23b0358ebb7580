"""Bring-up smoke of the OCCL runtime's main path on a TPU.

    python chip_smoke.py                 # one chip: phases A and B
    python chip_smoke.py --four-chips    # four chips: the mesh fabric only

Phase A, the trainer: ``repro.launch.train.run_occl_dp`` takes 3 steps of
data-parallel training with OCCL gradient sync (dp=2 simulated ranks on
the chip) on qwen3-0.6b at its published widths, cut to 4 of 28 layers
and one eighth of the vocabulary.  Every step's synced gradients are
checked against ``static_all_reduce`` and every loss must be finite.

Phase B, the paper's scenario: 4 simulated ranks register one collective
of each kind, each carrying one MLP weight gradient of that model, and
submit them in per-rank orders that deadlock a statically sequenced
library; ``drive()`` completes them and every result is checked against
numpy.  The all-reduce then runs again through the native Pallas slice
kernel and must equal the XLA path bit for bit.

``--four-chips`` runs the shard_map mesh backend over four chips: the
trainer's largest per-layer gradient buckets all-reduced in f32, one in
bf16 (the packed 16-bit exchange), an all-gather and an all-to-all, all
submitted in conflicting orders.  Each result is checked against numpy
and against XLA's own collective on the same mesh.

There is no CPU fallback: without a TPU the script exits non-zero before
any phase.  ``--rehearse`` is the CPU dress rehearsal: tiny shapes, the
Pallas kernel in the interpreter, and no result line.  Compile times and
peak memory are printed as information; the last line of stdout is
``{"ok": true, "device": {...}}`` and is printed only after every check
passed.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.core import (CollKind, OcclConfig, OcclRuntime,
                        registered_heap_elems, run_static_order)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import run_occl_dp
from repro.models import build_model
from repro.train.occl_moe import a2a_exchange_ref
from repro.train.occl_sync import static_all_reduce

# qwen3-0.6b widths: one MLP weight gradient is d_model x d_ff elements.
QWEN = get_config("qwen3-0.6b")
MLP_ELEMS = QWEN.d_model * QWEN.d_ff

# Sizes of the chip run, and the tiny ones of the CPU rehearsal.  The
# slicing (slice_elems x burst_slices elements per lane per superstep) is
# chosen so a 102M-element gradient sync takes thousands of supersteps,
# not the ~400k of the library default (256 x 1).
CHIP = dict(layers=4, vocab=QWEN.vocab // 8, seq=1024, per_rank_batch=2,
            dp=2, steps=3, sync_slice=65536, sync_burst=8,
            ranks=4, elems=MLP_ELEMS, slice=16384, burst=8)
REHEARSE = dict(layers=None, vocab=None, seq=32, per_rank_batch=2,
                dp=2, steps=3, sync_slice=64, sync_burst=8,
                ranks=4, elems=4 * 3 * 256, slice=64, burst=8)

# f32 sums taken in another order than numpy's differ by a few ulps of
# the largest partial sum; these bound that and nothing more.
RTOL, ATOL = 1e-5, 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit's compile time is its retrieval)."""

    def __init__(self):
        import jax.monitoring as mon

        self.secs = 0.0
        self.events = collections.Counter()
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, name, **_):
        self.events[name] += 1

    def report(self, label: str) -> None:
        log(f"compile [{label}]: backend compile {self.secs:.3f} s, "
            f"cache hits {self.events['/jax/compilation_cache/cache_hits']}, "
            f"misses {self.events['/jax/compilation_cache/cache_misses']}")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# ----------------------------------------------------------------------
# Phase A: the trainer
# ----------------------------------------------------------------------
def phase_a(sz: dict, device) -> None:
    import jax
    import jax.numpy as jnp

    if sz["layers"] is None:
        cfg = QWEN.reduced()
        log(f"phase A model: {cfg.name} reduced (rehearsal)")
    else:
        cfg = dataclasses.replace(QWEN, n_layers=sz["layers"],
                                  vocab=sz["vocab"])
        log(f"phase A model: {cfg.name} at published widths (d_model "
            f"{cfg.d_model}, {cfg.n_heads} q / {cfg.n_kv_heads} kv heads of "
            f"{cfg.d_head}, d_ff {cfg.d_ff}, qk_norm {cfg.qk_norm}, rope "
            f"theta {cfg.rope_theta:g})")
        log(f"phase A cut: depth {cfg.n_layers} of {QWEN.n_layers} layers "
            f"(the one layer kind is present)")
        log(f"phase A cut: vocabulary {cfg.vocab} of {QWEN.vocab} rows "
            f"(one chip's share when eight split it)")
    dp = sz["dp"]
    cell = ShapeCell("chip_smoke", sz["seq"], sz["per_rank_batch"] * dp,
                     "train")
    log(f"phase A batch: {sz['per_rank_batch']} x {sz['seq']} tokens per "
        f"rank, dp={dp}; sync slicing slice_elems={sz['sync_slice']} "
        f"burst_slices={sz['sync_burst']}")

    @jax.jit
    def compare(got, want):
        leaves = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda g, w: jnp.stack([
                jnp.all(jnp.abs(g - w) <= ATOL + RTOL * jnp.abs(w)),
                jnp.max(jnp.abs(g - w)) > 0, jnp.all(jnp.isfinite(g))]),
            got, want))
        s = jnp.stack(leaves)
        return jnp.all(s[:, 0]), jnp.sum(s[:, 1]), jnp.all(s[:, 2])

    def check(step, per_rank, synced):
        want = static_all_reduce(per_rank)
        for r in range(dp):
            close, differing, finite = (bool(x) for x in
                                        compare(synced[r], want[r]))
            assert finite, f"step {step} rank {r}: non-finite synced grads"
            assert close, (f"step {step} rank {r}: OCCL grads differ from "
                           "static_all_reduce beyond rtol/atol")
        log(f"phase A step {step}: synced grads match static_all_reduce "
            f"on all {dp} ranks ({int(differing)} leaves not bitwise equal "
            f"on rank {dp - 1})")

    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: build_model(cfg).init(0))))
    log(f"phase A params: {n_params} per rank")
    t0 = time.perf_counter()
    out = run_occl_dp(cfg, cell, sz["steps"], dp=dp,
                      slice_elems=sz["sync_slice"],
                      burst_slices=sz["sync_burst"], on_step=check)
    wall = time.perf_counter() - t0
    assert all(np.isfinite(out["losses"])), out["losses"]
    sync = out["sync"]
    log(f"phase A losses: {out['losses']}")
    log(f"phase A supersteps per step: {out['supersteps']} "
        f"({len(sync.buckets)} buckets, heap_elems "
        f"{sync.occl.cfg.heap_elems} per arena)")
    log(f"phase A wall: {wall:.3f} s for {sz['steps']} steps, "
        f"compile included; peak_bytes_in_use {peak_bytes(device)}")


# ----------------------------------------------------------------------
# Phase B: conflicting orders over every collective kind
# ----------------------------------------------------------------------
KINDS = [CollKind.ALL_REDUCE, CollKind.ALL_GATHER, CollKind.REDUCE_SCATTER,
         CollKind.BROADCAST, CollKind.REDUCE, CollKind.ALL_TO_ALL]
ROOTS = {CollKind.BROADCAST: 1, CollKind.REDUCE: 2}


def deadlocking_orders(n_colls: int, R: int) -> dict:
    """Rank r submits the collectives rotated by r: no collective is at
    the head of every rank's queue, so a static library wedges at once."""
    orders = {r: [(i + r) % n_colls for i in range(n_colls)]
              for r in range(R)}
    static = run_static_order(
        orders, {c: list(range(R)) for c in range(n_colls)})
    assert static.deadlocked and static.cycle, static
    log(f"static single-queue order: deadlocked, wait-for cycle over "
        f"ranks {static.cycle}")
    return orders


def kind_inputs(kind, n: int, R: int, rng) -> list:
    per_rank = n // R if kind == CollKind.ALL_GATHER else n
    return [rng.standard_normal(per_rank, dtype=np.float32)
            for _ in range(R)]


def kind_reference(kind, xs: list, R: int) -> list:
    """Expected output per rank (None where the kind defines none)."""
    if kind == CollKind.ALL_GATHER:
        return [np.concatenate(xs)] * R
    if kind == CollKind.ALL_TO_ALL:
        return a2a_exchange_ref(xs)
    if kind == CollKind.BROADCAST:
        return [xs[ROOTS[kind]]] * R
    total = np.sum(np.stack(xs).astype(np.float64), axis=0)
    if kind == CollKind.REDUCE_SCATTER:
        c = total.size // R
        return [total[r * c:(r + 1) * c] for r in range(R)]
    if kind == CollKind.REDUCE:
        return [total if r == ROOTS[kind] else None for r in range(R)]
    return [total] * R


def check_output(what: str, got, want) -> None:
    if want is None:
        return
    if want.dtype == np.float64:            # a reduction: order may differ
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL,
                                   err_msg=what)
    else:                                   # pure data movement: exact
        assert got.tobytes() == want.tobytes(), f"{what}: not bit-exact"


def scenario_runtime(cfg: OcclConfig, colls: list, mesh=None) -> tuple:
    """A runtime whose heap fits ``colls`` ((kind, n_elems) pairs), all
    registered on one communicator over every rank; returns it and the
    collective handles."""
    def register(rt):
        comm = rt.communicator(list(range(cfg.n_ranks)))
        return [rt.register(kind, comm, n_elems=n,
                            root=ROOTS.get(kind, 0)) for kind, n in colls]

    heap = registered_heap_elems(cfg, register)
    rt = OcclRuntime(dataclasses.replace(cfg, heap_elems=heap), mesh=mesh)
    return rt, register(rt)


def phase_b(sz: dict, device, interpret: bool) -> None:
    R, n = sz["ranks"], sz["elems"]
    cfg = OcclConfig(n_ranks=R, max_colls=8, max_comms=1,
                     slice_elems=sz["slice"], burst_slices=sz["burst"],
                     conn_depth=3 * sz["burst"], sq_len=16,
                     superstep_budget=1 << 16)
    log(f"phase B: R={R} simulated ranks, {len(KINDS)} kinds x {n} elems, "
        f"slice_elems={cfg.slice_elems} burst_slices={cfg.burst_slices} "
        f"conn_depth={cfg.conn_depth}")
    orders = deadlocking_orders(len(KINDS), R)
    rt, ids = scenario_runtime(cfg, [(k, n) for k in KINDS])
    rng = np.random.default_rng(0)
    inputs = [kind_inputs(k, n, R, rng) for k in KINDS]
    t0 = time.perf_counter()
    for r in range(R):
        for slot in orders[r]:
            kind = KINDS[slot]
            feeds = kind != CollKind.BROADCAST or r == ROOTS[kind]
            rt.submit(r, ids[slot], data=inputs[slot][r] if feeds else None)
    rt.drive()
    wall = time.perf_counter() - t0
    st = rt.stats()
    log(f"phase B drive: {wall:.3f} s compile included, supersteps "
        f"{int(st['supersteps'].max())}, launches {rt.launches}, "
        f"preempts {int(st['preempts'].sum())}")
    assert int(st["preempts"].sum()) > 0, "no preemption resolved the orders"
    got = rt.read_outputs_bulk([(r, c) for r in range(R) for c in ids])
    for slot, kind in enumerate(KINDS):
        want = kind_reference(kind, inputs[slot], R)
        for r in range(R):
            check_output(f"{kind.name} rank {r}", got[(r, ids[slot])],
                         want[r])
    log(f"phase B: all {len(KINDS)} kinds complete and match numpy on "
        f"all {R} ranks")

    # The all-reduce again through the fused Pallas slice kernel.
    prt, (pid,) = scenario_runtime(
        dataclasses.replace(cfg, use_pallas=True, pallas_interpret=interpret),
        [(CollKind.ALL_REDUCE, n)])
    for r in range(R):
        prt.submit(r, pid, data=inputs[0][r])
    t0 = time.perf_counter()
    prt.drive()
    log(f"phase B pallas all-reduce ({'interpreted' if interpret else 'native'}"
        f"): {time.perf_counter() - t0:.3f} s compile included, supersteps "
        f"{int(prt.stats()['supersteps'].max())}")
    for r in range(R):
        a, b = prt.read_output(r, pid), got[(r, ids[0])]
        # f32 and one elementwise op per slice combine: bitwise equal.
        assert a.tobytes() == b.tobytes(), f"pallas != xla on rank {r}"
    log(f"phase B: pallas all-reduce equals the XLA path bitwise; "
        f"peak_bytes_in_use {peak_bytes(device)}")


# ----------------------------------------------------------------------
# Four chips: the mesh fabric against XLA's collectives
# ----------------------------------------------------------------------
def four_chips(sz: dict) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    assert len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}"
    R, n = 4, sz["elems"]
    mesh = jax.make_mesh((R,), ("rank",), devices=devs[:R])
    sharded = NamedSharding(mesh, P("rank"))
    cfg = OcclConfig(n_ranks=R, max_colls=8, max_comms=1,
                     slice_elems=sz["slice"], burst_slices=sz["burst"],
                     conn_depth=3 * sz["burst"], sq_len=16,
                     superstep_budget=1 << 16)
    rng = np.random.default_rng(1)

    def xla(fn):
        return jax.jit(jax.shard_map(lambda x: fn(x[0])[None], mesh=mesh,
                                     in_specs=P("rank"),
                                     out_specs=P("rank")))

    xla_ops = {
        CollKind.ALL_REDUCE: xla(lambda x: jax.lax.psum(x, "rank")),
        CollKind.ALL_GATHER: xla(
            lambda x: jax.lax.all_gather(x, "rank", tiled=True)),
        CollKind.ALL_TO_ALL: xla(lambda x: jax.lax.all_to_all(
            x.reshape(R, -1), "rank", 0, 0, tiled=True).reshape(-1)),
    }

    def run(label, colls, dtype, make_input):
        """Two rounds of conflicting-order submission; the second (warm)
        is timed and checked against numpy and XLA."""
        rt, ids = scenario_runtime(dataclasses.replace(cfg, dtype=dtype),
                                   [(k, n) for _, k in colls], mesh=mesh)
        orders = {r: [(i + r) % len(colls) for i in range(len(colls))]
                  for r in range(R)}
        for rnd in range(2):
            xs = [[make_input(k) for _ in range(R)] for _, k in colls]
            t0 = time.perf_counter()
            for r in range(R):
                for slot in orders[r]:
                    rt.submit(r, ids[slot], data=xs[slot][r])
            rt.drive()
            wall = time.perf_counter() - t0
        got = rt.read_outputs_bulk([(r, c) for r in range(R) for c in ids])
        st = rt.stats()
        log(f"four chips [{label}]: occl submit->drive {wall:.6f} s warm "
            f"(payload upload included), supersteps "
            f"{int(st['supersteps'].max())} over 2 rounds, preempts "
            f"{int(st['preempts'].sum())}, sharded staging flushes "
            f"{st['staging_sharded_flushes']} of {st['staging_flush_writes']}")
        for slot, (name, kind) in enumerate(colls):
            want = kind_reference(kind, [x.astype(np.float32)
                                         for x in xs[slot]], R)
            glob = jax.device_put(np.stack(xs[slot]), sharded)
            op = xla_ops[kind]
            jax.block_until_ready(op(glob))
            t0 = time.perf_counter()
            ref = jax.block_until_ready(op(glob))
            t_xla = time.perf_counter() - t0
            ref = np.asarray(ref)
            for r in range(R):
                g = got[(r, ids[slot])]
                check_output(f"{label} {name} rank {r} vs numpy",
                             g.astype(np.float32), want[r])
                if kind == CollKind.ALL_REDUCE and dtype == "float32":
                    np.testing.assert_allclose(g, ref[r], rtol=RTOL,
                                               atol=ATOL)
                else:
                    assert g.tobytes() == ref[r].tobytes(), (
                        f"{label} {name} rank {r}: differs from XLA")
            log(f"four chips [{label}] {name}: matches numpy and XLA; "
                f"xla {kind.name.lower()} {t_xla:.6f} s")

    def f32_input(kind):
        m = n // R if kind == CollKind.ALL_GATHER else n
        return rng.standard_normal(m, dtype=np.float32)

    def bf16_input(kind):
        # Multiples of 1/8 in [-8, 8): every partial sum of four is exact
        # in bf16, so the packed 16-bit exchange is checked bit for bit.
        import ml_dtypes
        return (rng.integers(-64, 64, n) / 8).astype(ml_dtypes.bfloat16)

    run("f32", [("mlp.w_gate grad", CollKind.ALL_REDUCE),
                ("mlp.w_up grad", CollKind.ALL_REDUCE),
                ("mlp.w_down grad", CollKind.ALL_REDUCE),
                ("all-gather", CollKind.ALL_GATHER),
                ("all-to-all", CollKind.ALL_TO_ALL)],
        "float32", f32_input)
    run("bf16", [("mlp.w_down grad", CollKind.ALL_REDUCE)],
        "bfloat16", bf16_input)
    for d in devs[:4]:
        log(f"four chips: {d} peak_bytes_in_use {peak_bytes(d)}")


# ----------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dress rehearsal at tiny sizes; prints no "
                         "result line")
    args = ap.parse_args()
    sz = REHEARSE if args.rehearse else CHIP

    cache = enable_compile_cache()
    clock = CompileClock()
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    log(f"compile cache: {cache}")
    if dev.platform != "tpu" and not args.rehearse:
        print("chip_smoke: no TPU found; this script has no CPU fallback",
              file=sys.stderr)
        return 2
    if args.four_chips:
        four_chips(sz)
        clock.report("four chips")
    else:
        phase_a(sz, dev)
        clock.report("after phase A")
        phase_b(sz, dev, interpret=args.rehearse)
        clock.report("after phase B")
    if args.rehearse:
        log("rehearsal passed at tiny sizes on "
            f"{dev.platform}: not a chip result")
        return 0
    log(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
