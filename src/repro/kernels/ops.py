"""jit'd dispatch layer over the Pallas kernels.

The kernels compile natively for the TPU.  The Pallas interpreter runs
them only where the caller asks for it by name (``interpret=True``; the
runtime's ``OcclConfig.pallas_interpret``), which is how CPU tests
validate the kernel bodies against the ref.py oracles.  Nothing here
looks at the backend: a kernel asked to compile natively off a TPU fails
loudly instead of falling back to the interpreter.
"""
from __future__ import annotations

from . import ref
from .chunk_combine import chunk_combine_pallas
from .fused_slice import fused_primitive_pallas


def fused_primitive_batch(payload, local, flags, *, interpret: bool = False):
    """Scheduler entry point: the whole [L*B, SLICE] superstep burst —
    every lane's slice burst, with per-row (recv, reduce, reads_in, op)
    opcodes — in ONE kernel call."""
    return fused_primitive_pallas(payload, local, flags, interpret=interpret)


def chunk_combine(a, b, op: int = 0, *, interpret: bool = False):
    return chunk_combine_pallas(a, b, op, interpret=interpret)


# ref aliases, exported for benchmarks and tests
fused_primitive_ref = ref.fused_primitive_ref
chunk_combine_ref = ref.chunk_combine_ref
