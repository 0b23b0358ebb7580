"""Pallas TPU kernel: fused primitive slice application.

The daemon's compute hot-spot is the fused action of a primitive on a
slice (paper Sec. 2.3): ``recvReduceCopySend`` reads the recv-connector
payload and the local send buffer once, combines them, and feeds both the
recv-buffer write and the send-connector push from the same value — one
pass through VMEM instead of separate reduce + copy kernels.

Layout: payload/local are [N, S], where the scheduler batches the FULL
superstep burst into N = L * burst_slices rows (every lane's contiguous
slice burst) and S = slice_elems — one kernel call per superstep instead of
one per lane per slice.  Grid is (N // RB, S // TS): each program instance
owns an (RB, TS) VMEM tile, with RB = 8 rows when N is a multiple of 8 and
all N rows otherwise, and TS a multiple of 128 dividing S (or S itself) —
the TPU's (8, 128) tiling rule for the last two block dimensions.  The
per-row opcodes (recv, reduce, reads_in, op) are scalar-prefetched into
SMEM as one flat [N * 4] vector; each tile applies them row by row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8


def _row_block(n: int) -> int:
    return _ROWS if n % _ROWS == 0 else n


def _lane_tile(s: int) -> int:
    # Largest lane-aligned tile dividing S; S itself when none does.
    for ts in (8192, 4096, 2048, 1024, 512, 256, 128):
        if s % ts == 0:
            return ts
    return s


def _kernel(flags_ref, payload_ref, local_ref, out_ref, *, rows: int):
    r0 = pl.program_id(0) * rows
    for i in range(rows):
        base = (r0 + i) * 4
        recv = flags_ref[base] > 0
        reduce = flags_ref[base + 1] > 0
        reads = flags_ref[base + 2] > 0
        op = flags_ref[base + 3]
        # bf16 combines accumulate in f32 (matches the ref.py oracle).
        pf = payload_ref[pl.ds(i, 1), :].astype(jnp.float32)
        lf = local_ref[pl.ds(i, 1), :].astype(jnp.float32)
        combined = jnp.where(
            op == 0, pf + lf,
            jnp.where(op == 1, jnp.maximum(pf, lf),
                      jnp.where(op == 2, jnp.minimum(pf, lf), pf * lf)))
        val = jnp.where(
            reduce, combined,
            jnp.where(recv, pf, jnp.where(reads, lf, jnp.zeros_like(lf))))
        out_ref[pl.ds(i, 1), :] = val.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_primitive_pallas(payload: jnp.ndarray, local: jnp.ndarray,
                           flags: jnp.ndarray, *,
                           interpret: bool = False) -> jnp.ndarray:
    """payload, local: [N, S]; flags: [N, 4] i32 -> value [N, S].

    ``interpret=True`` runs the Pallas interpreter (CPU callers and
    tests); the default compiles the kernel for the TPU."""
    N, S = payload.shape
    RB, TS = _row_block(N), _lane_tile(S)
    tile = pl.BlockSpec((RB, TS), lambda b, s, flags: (b, s))
    return pl.pallas_call(
        functools.partial(_kernel, rows=RB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // RB, S // TS),
            in_specs=[tile, tile],
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct((N, S), payload.dtype),
        interpret=interpret,
    )(flags.astype(jnp.int32).reshape(N * 4), payload, local)
