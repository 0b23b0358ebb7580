"""Host spans of the OCCL hot path, on the profiler's own clock.

Each span is a ``jax.profiler.TraceAnnotation`` named ``occl.<name>``: it
lands in the profiler's trace beside the device ops, so an idle gap on the
device can be named by the host work under it.  Nothing is recorded on the
host; without a profiler session a span costs about a microsecond.

The six phases of a grad-sync step are siblings and never nest:

* ``occl.pack`` (``bytes``): ``OcclGradSync`` copies one rank's device
  leaves of one bucket into a flat host buffer (D2H, concatenate).
* ``occl.submit`` (``bytes``, 0 without a payload): ``OcclRuntime.submit``
  validates, snapshots the payload for staging and enqueues the SQE.
* ``occl.flush`` (``bytes``, ``items``): the launch prologue ships what was
  staged (concatenate, H2D, dispatch of the write plan); only when
  something is staged.
* ``occl.launch`` (``tick_k``, 0 for the one-shot daemon): the rest of
  ``launch_once`` -- SQ pack, daemon dispatch, the wait on the device,
  CQE reconciliation.
* ``occl.read`` (``bytes``): ``read_outputs_bulk`` (read plan, D2H, un-pad
  copies; on the CPU straight out of the heap view).
* ``occl.unpack`` (``bytes``): ``OcclGradSync`` divides, reshapes and
  uploads every leaf of every rank.

``occl.plan_build`` (``kind`` ``write``/``read``, ``bytes``; a write adds
``path``: ``runs``, ``gather`` or ``sharded``) nests inside a flush or a
read: a staging plan missed its cache and was built (its runs or index
maps, their upload); the plan's first call, in the enclosing span,
compiles it.

Capture with ``jax.profiler.trace(dir)`` and open the trace in Perfetto or
TensorBoard.
"""
from __future__ import annotations

import jax


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """``with span("flush", bytes=n): ...`` -- the span ``occl.<name>``
    with ``stats`` as its arguments in the trace.  A stat known only at
    the end is added with ``set_metadata`` on the entered span."""
    return jax.profiler.TraceAnnotation(f"occl.{name}", **stats)
