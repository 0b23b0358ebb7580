"""Pallas TPU kernel: bulk chunk combine (the ring-reduce workhorse).

``recvReduceSend`` over a whole chunk at bandwidth: elementwise combine of
two flat buffers with f32 accumulation for bf16 wire payloads.  Used by the
bulk static-path collectives (grad-bucket ring reduce) where whole chunks
move per superstep rather than single slices.

Grid: 1-D over tiles of TILE elements; each instance streams one VMEM tile
of ``a`` and ``b`` and writes one tile of the result — HBM traffic is
exactly 2 reads + 1 write per element (roofline-optimal for this op).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 1024


def _kernel(a_ref, b_ref, o_ref, *, op: int):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    if op == 0:
        v = a + b
    elif op == 1:
        v = jnp.maximum(a, b)
    elif op == 2:
        v = jnp.minimum(a, b)
    else:
        v = a * b
    o_ref[...] = v.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def chunk_combine_pallas(a: jnp.ndarray, b: jnp.ndarray, op: int = 0, *,
                         interpret: bool = False) -> jnp.ndarray:
    """Elementwise combine of flat [T] buffers (T padded to TILE).

    ``interpret=True`` runs the Pallas interpreter (CPU callers and
    tests); the default compiles the kernel for the TPU."""
    (T,) = a.shape
    pad = (-T) % TILE
    if pad:
        a = jnp.pad(a, (0, pad))
        b = jnp.pad(b, (0, pad))
    n = (T + pad) // TILE
    out = pl.pallas_call(
        functools.partial(_kernel, op=op),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((TILE,), lambda i: (i,)),
            pl.BlockSpec((TILE,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((T + pad,), a.dtype),
        interpret=interpret,
    )(a, b)
    return out[:T]
