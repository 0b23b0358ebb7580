"""Device-resident staging engine: bulk ≡ scalar heap I/O, stale-padding
regression, result ownership, per-SQE dynamic offsets, prologue flush.

Regression background: the old ``write_inputs_bulk`` mirrored the whole
heap through host memory and wrote ONLY the logical elements of each
chunk, so pad positions kept whatever the heap held before — stale data
from a prior step leaked into the padded slices the daemon circulates
(the scalar ``write_input`` always zero-filled its staging buffer).  The
old read paths returned numpy views aliasing the heap snapshot, and
per-SQE ``in_off``/``out_off`` overrides were honored by the daemon but
silently ignored by the host I/O paths.  The staging engine closes all
three: pads are part of every fused scatter, reads return owned copies,
and offset overrides are scalar adds on the precomputed index maps.

These deterministic cases double as the fallback for the hypothesis
sweep in test_staging_props.py (which skips without hypothesis).
"""
import numpy as np
import pytest

from repro.core import CollKind, OcclConfig, OcclRuntime, ReduceOp


def _cfg(**kw):
    base = dict(n_ranks=4, max_colls=4, max_comms=1, slice_elems=8,
                conn_depth=4, heap_elems=1 << 13)
    base.update(kw)
    return OcclConfig(**base)


def _ragged_sizes(n, R):
    """Per-distance live counts with real capacity drops at odd n."""
    cl = -(-n // R)
    return tuple(max(0, cl - 2 * d) for d in range(R))


def _register(rt, kind, comm, n, **kw):
    """Kind-aware registration: the a2a family has contracts the original
    five kinds don't (exactly-divisible totals; the ragged variant takes
    explicit per-distance live sizes)."""
    R = len(comm.members)
    if kind == CollKind.ALL_TO_ALL:
        return rt.register(kind, comm, n_elems=n - n % R, **kw)
    if kind == CollKind.ALL_TO_ALL_RAGGED:
        return rt.register(kind, comm, n_elems=n,
                           chunk_sizes=_ragged_sizes(n, R), **kw)
    return rt.register(kind, comm, n_elems=n, **kw)


def _inputs(kind, n, R, seed=0):
    rng = np.random.RandomState(seed)
    chunk = -(-n // R)
    if kind == CollKind.ALL_GATHER:
        return [rng.randn(chunk).astype(np.float32) for _ in range(R)]
    if kind == CollKind.ALL_TO_ALL:
        n = n - n % R
    elif kind == CollKind.ALL_TO_ALL_RAGGED:
        n = sum(_ragged_sizes(n, R))
    return [rng.randn(n).astype(np.float32) for _ in range(R)]


def _pollute(rt, fill=7.5):
    """Overwrite the input heap with garbage, simulating stale data from a
    prior step that reused the region (e.g. via dynamic offsets)."""
    import jax.numpy as jnp
    rt._ensure_built()
    rt._state = rt._state._replace(
        heap_in=jnp.full_like(rt._state.heap_in, fill))


@pytest.mark.parametrize("kind", list(CollKind))
def test_bulk_write_matches_scalar_on_polluted_heap(kind):
    """THE stale-padding regression: over a garbage-filled heap, the bulk
    write must leave the heap bit-identical to the scalar path — in
    particular, pad positions must be ZERO, not stale garbage.  The old
    write_inputs_bulk fails this (it wrote only logical elements)."""
    R, n = 4, 53                                   # odd: real pad tails
    xs = _inputs(kind, n, R)

    rts = []
    for _ in range(2):
        rt = OcclRuntime(_cfg())
        comm = rt.communicator(list(range(R)))
        cid = _register(rt, kind, comm, n)
        _pollute(rt)
        rts.append((rt, cid))

    (rt_scalar, cid), (rt_bulk, _) = rts
    for r in range(R):
        rt_scalar.write_input(r, cid, xs[r])
    rt_bulk.write_inputs_bulk({(r, cid): xs[r] for r in range(R)})

    h_scalar = np.asarray(rt_scalar.state.heap_in)
    h_bulk = np.asarray(rt_bulk.state.heap_in)
    np.testing.assert_array_equal(h_bulk, h_scalar)

    # Explicit pad check: inside the written span, every non-logical
    # position is zero (write_input's zero-fill guarantee).
    t = rt_bulk._tables
    spec = rt_bulk.specs[cid]
    span = int(t.in_span[cid])
    # Pad positions derived independently of the engine's mask: every
    # in-span offset the logical map does not cover.
    pad_rel = np.setdiff1d(np.arange(span, dtype=np.int32),
                           t.stage_in_map[cid])
    assert span > int(t.in_log[cid]), "test needs a real pad tail"
    for r in range(R):
        region = h_bulk[r, spec.in_off:spec.in_off + span]
        np.testing.assert_array_equal(region[pad_rel], 0.0)


@pytest.mark.parametrize("kind", list(CollKind))
def test_bulk_roundtrip_equals_scalar_roundtrip(kind):
    """write_inputs_bulk -> drive -> read_outputs_bulk ≡ the scalar
    write_input -> drive -> read_output pipeline, for every CollKind at an
    odd size, over THREE reuses of the same heap (stale-state regression)."""
    R, n = 4, 37
    rt_s = OcclRuntime(_cfg())
    rt_b = OcclRuntime(_cfg())
    comms = [rt.communicator(list(range(R))) for rt in (rt_s, rt_b)]
    cids = [_register(rt, kind, comm, n)
            for rt, comm in zip((rt_s, rt_b), comms)]

    for step in range(3):
        xs = _inputs(kind, n, R, seed=step)
        for r in range(R):
            data = xs[0] if kind == CollKind.BROADCAST else xs[r]
            rt_s.write_input(r, cids[0], data)
            rt_s.submit(r, cids[0])
        rt_b.write_inputs_bulk({
            (r, cids[1]): (xs[0] if kind == CollKind.BROADCAST else xs[r])
            for r in range(R)})
        for r in range(R):
            rt_b.submit(r, cids[1])
        rt_s.drive()
        rt_b.drive()
        bulk = rt_b.read_outputs_bulk([(r, cids[1]) for r in range(R)])
        for r in range(R):
            np.testing.assert_array_equal(bulk[(r, cids[1])],
                                          rt_s.read_output(r, cids[0]))


def test_read_results_are_owned_and_mutation_safe():
    """Aliasing regression: results are writable owned copies; in-place
    mutation (the grad-sync ``/= n_ranks``) cannot corrupt sibling reads
    or re-reads.  The old non-chunked read paths returned views of the
    heap snapshot."""
    R, n = 2, 24
    rt = OcclRuntime(_cfg(n_ranks=R))
    comm = rt.communicator([0, 1])
    cid = rt.register(CollKind.REDUCE_SCATTER, comm, n_elems=n)  # non-chunked out
    xs = _inputs(CollKind.REDUCE_SCATTER, n, R)
    for r in range(R):
        rt.submit(r, cid, data=xs[r])
    rt.drive()

    o1 = rt.read_output(0, cid)
    assert o1.flags.writeable and o1.flags.owndata
    ref = o1.copy()
    o1 /= R                                        # must not corrupt anything
    np.testing.assert_array_equal(rt.read_output(0, cid), ref)

    bulk = rt.read_outputs_bulk([(r, cid) for r in range(R)])
    keep = bulk[(1, cid)].copy()
    bulk[(0, cid)][:] = -1.0
    np.testing.assert_array_equal(bulk[(1, cid)], keep)
    np.testing.assert_array_equal(rt.read_output(0, cid), ref)


def test_sqe_dynamic_offsets_honored_end_to_end():
    """A submission overriding in_off/out_off runs entirely in the
    override region: staged payloads land there, the daemon reads/writes
    there, and the registered default region stays untouched.  The old
    host paths silently ignored the override (daemon read zeros)."""
    R, n = 2, 33
    rt = OcclRuntime(_cfg(n_ranks=R))
    comm = rt.communicator([0, 1])
    a = rt.register(CollKind.ALL_REDUCE, comm, n_elems=n)
    b = rt.register(CollKind.ALL_REDUCE, comm, n_elems=n)  # reserves a twin region
    alt = rt.specs[b]
    xs = _inputs(CollKind.ALL_REDUCE, n, R)
    for r in range(R):
        rt.submit(r, a, data=xs[r], in_off=alt.in_off, out_off=alt.out_off)
    rt.drive()
    want = xs[0] + xs[1]
    for r in range(R):
        np.testing.assert_allclose(
            rt.read_output(r, a, out_off=alt.out_off), want,
            rtol=1e-5, atol=1e-6)
        assert not rt.read_output(r, a).any()      # default region untouched
    # bulk variants accept the same overrides
    rt.write_inputs_bulk({(0, a): (xs[0], alt.in_off)})
    got = rt.read_outputs_bulk([(0, a, alt.out_off)])
    np.testing.assert_allclose(got[(0, a)], want, rtol=1e-5, atol=1e-6)


def test_out_of_range_offset_rejected():
    rt = OcclRuntime(_cfg(n_ranks=2))
    comm = rt.communicator([0, 1])
    cid = rt.register(CollKind.ALL_REDUCE, comm, n_elems=16)
    with pytest.raises(ValueError, match="in_off override"):
        rt.submit(0, cid, data=np.zeros(16, np.float32),
                  in_off=rt.cfg.heap_elems - 1)
    with pytest.raises(ValueError, match="out_off override"):
        rt.read_output(0, cid, out_off=rt.cfg.heap_elems - 1)


def test_wrong_payload_size_rejected():
    """The bulk path now carries the size validation write_input had —
    as ValueError, so it survives python -O."""
    rt = OcclRuntime(_cfg(n_ranks=2))
    comm = rt.communicator([0, 1])
    cid = rt.register(CollKind.ALL_REDUCE, comm, n_elems=16)
    with pytest.raises(ValueError, match="logical size"):
        rt.write_inputs_bulk({(0, cid): np.zeros(15, np.float32)})
    with pytest.raises(ValueError, match="logical size"):
        rt.submit(0, cid, data=np.zeros(17, np.float32))


def test_submit_payloads_flush_in_launch_prologue():
    """submit(data=...) must NOT touch the device at call time: payloads
    park in the staging queue and flush as one batched scatter in the
    launch prologue; an explicit write_input supersedes the staged entry."""
    R, n = 2, 16
    rt = OcclRuntime(_cfg(n_ranks=R))
    comm = rt.communicator([0, 1])
    cid = rt.register(CollKind.ALL_REDUCE, comm, n_elems=n)
    xs = _inputs(CollKind.ALL_REDUCE, n, R)
    for r in range(R):
        rt.submit(r, cid, data=xs[r])
    assert len(rt.queues.staged) == R
    assert not np.asarray(rt.state.heap_in).any()  # nothing written yet

    # A later direct write supersedes rank 0's staged payload (last write
    # at the same buffer wins, matching the old immediate-write semantics).
    override = 2 * xs[0]
    rt.write_input(0, cid, override)
    assert (0, cid, rt.specs[cid].in_off) not in rt.queues.staged

    rt.drive()
    assert len(rt.queues.staged) == 0
    want = override + xs[1]
    for r in range(R):
        np.testing.assert_allclose(rt.read_output(r, cid), want,
                                   rtol=1e-5, atol=1e-6)


def test_staged_payload_is_snapshotted_at_submit_time():
    """Mutating the caller's buffer between submit(data=...) and drive()
    must not change what lands in the heap (the pre-PR immediate-write
    path captured the value at call time; the staging queue must too)."""
    rt = OcclRuntime(_cfg(n_ranks=2))
    comm = rt.communicator([0, 1])
    cid = rt.register(CollKind.ALL_REDUCE, comm, n_elems=16)
    x = np.ones(16, np.float32)
    rt.submit(0, cid, data=x)
    rt.submit(1, cid, data=np.ones(16, np.float32))
    x *= 100.0                                     # reused caller buffer
    rt.drive()
    np.testing.assert_allclose(rt.read_output(0, cid),
                               2 * np.ones(16), rtol=1e-6)


def test_restaging_same_collective_last_write_wins():
    rt = OcclRuntime(_cfg(n_ranks=2))
    comm = rt.communicator([0, 1])
    cid = rt.register(CollKind.ALL_REDUCE, comm, n_elems=8)
    rt.submit(0, cid, data=np.ones(8, np.float32))
    rt.queues.pending[0].pop()                     # drop the duplicate SQE
    rt.queues.submitted[0] -= 1
    rt.submit(0, cid, data=3 * np.ones(8, np.float32))
    rt.submit(1, cid, data=np.ones(8, np.float32))
    rt.drive()
    np.testing.assert_allclose(rt.read_output(0, cid),
                               4 * np.ones(8, np.float32), rtol=1e-6)


def test_two_staged_submissions_at_distinct_offsets_both_land():
    """Pre-flush submissions of the SAME collective at different dynamic
    offsets are distinct executions: both payloads must survive staging
    (the queue is keyed by offset, not just (rank, collective)) and both
    results must be readable at their own offsets."""
    R, n = 2, 17
    rt = OcclRuntime(_cfg(n_ranks=R))
    comm = rt.communicator([0, 1])
    a = rt.register(CollKind.ALL_REDUCE, comm, n_elems=n)
    b = rt.register(CollKind.ALL_REDUCE, comm, n_elems=n)  # twin region
    alt = rt.specs[b]
    xs = _inputs(CollKind.ALL_REDUCE, n, R, seed=1)
    ys = _inputs(CollKind.ALL_REDUCE, n, R, seed=2)
    for r in range(R):
        rt.submit(r, a, data=xs[r])                       # default buffers
        rt.submit(r, a, data=ys[r], in_off=alt.in_off,
                  out_off=alt.out_off)                    # override buffers
    assert len(rt.queues.staged) == 2 * R                 # nothing dropped
    rt.drive()
    for r in range(R):
        np.testing.assert_allclose(rt.read_output(r, a), xs[0] + xs[1],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            rt.read_output(r, a, out_off=alt.out_off), ys[0] + ys[1],
            rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="conflicting out_off"):
        rt.read_outputs_bulk([(0, a), (0, a, alt.out_off)])
    # identical repeats still dedup silently (pre-PR dict semantics)
    dup = rt.read_outputs_bulk([(0, a), (0, a)])
    assert set(dup) == {(0, a)}


@pytest.mark.parametrize("kind", [CollKind.ALL_REDUCE, CollKind.ALL_GATHER,
                                  CollKind.REDUCE_SCATTER])
def test_device_read_plan_matches_host_fast_path(kind, monkeypatch):
    """The compiled segment-gather read plan (the accelerator branch the
    CPU zero-copy fast path short-circuits) must return the same owned
    results — covered here by disabling the fast path, in every caller
    order (permutation-independent plan cache)."""
    from repro.core import staging as staging_mod
    R, n = 4, 53                                   # odd: padded layouts
    rt = OcclRuntime(_cfg())
    comm = rt.communicator(list(range(R)))
    cid = rt.register(kind, comm, n_elems=n)
    xs = _inputs(kind, n, R)
    for r in range(R):
        rt.submit(r, cid, data=xs[r])
    rt.drive()
    want = rt.read_outputs_bulk([(r, cid) for r in range(R)])
    monkeypatch.setattr(staging_mod, "_host_is_device", lambda: False)
    for order in ([0, 1, 2, 3], [3, 1, 0, 2]):
        got = rt.read_outputs_bulk([(r, cid) for r in order])
        for r in range(R):
            np.testing.assert_array_equal(got[(r, cid)], want[(r, cid)])
            assert got[(r, cid)].flags.writeable
    assert len(rt._staging._read_plans) == 1       # permutations share one


def test_reduce_op_with_staged_inputs():
    """Staged path composes with non-SUM ops (MAX over negatives would
    expose any zero-pad leak into logical positions)."""
    rt = OcclRuntime(_cfg(n_ranks=2))
    comm = rt.communicator([0, 1])
    cid = rt.register(CollKind.ALL_REDUCE, comm, n_elems=21,
                      op=ReduceOp.MAX)
    xs = [-1 - np.arange(21, dtype=np.float32),
          -2 - np.arange(21, dtype=np.float32)]
    for r in range(2):
        rt.submit(r, cid, data=xs[r])
    rt.drive()
    for r in range(2):
        np.testing.assert_allclose(rt.read_output(r, cid),
                                   np.maximum(xs[0], xs[1]), rtol=1e-6)


# -- write plans: contiguous run copies against the element gather ---------

def _reference_heap(rt, heap, writes):
    """Each write's ``np.where(mask, vals[src], 0)`` over its padded span,
    scattered into ``heap`` in (rank, offset) order: the element gather,
    kept here as the reference every write plan matches bit for bit."""
    t = rt._tables
    heap = heap.copy()
    for rank, cid, data, off in sorted(writes, key=lambda w: (w[0], w[3])):
        span = int(t.in_span[cid])
        m = t.stage_in_map[cid]
        src = np.zeros(span, np.int64)
        mask = np.zeros(span, bool)
        src[m] = np.arange(m.size)
        mask[m] = True
        vals = np.asarray(data).astype(heap.dtype)
        heap[rank, off:off + span] = np.where(mask, vals[src],
                                              heap.dtype.type(0))
    return heap


def _write_case(case):
    """(runtime, writes as (rank, cid, data, in_off), expected plan path)
    of one layout the write plan has to pack."""
    R = 4
    cfg = {"bf16_heap": dict(dtype="bfloat16"),
           "in_perm_all_to_all": dict(max_comms=3)}.get(case, {})
    rt = OcclRuntime(_cfg(**cfg))
    comm = rt.communicator(list(range(R)))
    if case == "in_perm_all_to_all":
        # The two-level plan's granule transposes: the head stage's
        # input map splits into more runs than it has chunks.
        cids = [rt.register(CollKind.ALL_TO_ALL, comm, n_elems=32,
                            algo="two_level", hierarchy=(2, 2))]
    else:
        kinds = {"ceil_chunk_all_reduce": [(CollKind.ALL_REDUCE, 53)],
                 "pad_free_identity": [(CollKind.BROADCAST, 32)],
                 "flat_all_to_all": [(CollKind.ALL_TO_ALL, 54)],
                 "ragged_zero_chunk": [(CollKind.ALL_TO_ALL_RAGGED, 9)],
                 "bf16_heap": [(CollKind.ALL_REDUCE, 53)],
                 "offset_overrides": [(CollKind.ALL_REDUCE, 33),
                                      (CollKind.ALL_REDUCE, 33)],
                 # Same padded span, different live tails: one dense
                 # block whose rows are not alike.
                 "stacked_unlike_rows": [(CollKind.ALL_REDUCE, 53),
                                         (CollKind.ALL_REDUCE, 50)],
                 "all_rank_stack": [(CollKind.ALL_REDUCE, 53),
                                    (CollKind.ALL_GATHER, 21),
                                    (CollKind.REDUCE_SCATTER, 37)]}[case]
        cids = [_register(rt, kind, comm, n) for kind, n in kinds]
    rt._ensure_built()
    t = rt._tables
    rng = np.random.RandomState(sum(map(ord, case)))
    writes = []
    for cid in cids[:1] if case == "offset_overrides" else cids:
        for r in range(R):
            off = rt.specs[cid].in_off
            if case == "offset_overrides" and r % 2:
                off = rt.specs[cids[1]].in_off    # the twin region
            if case == "stacked_unlike_rows":
                # Even ranks write the first, odd ranks the second, both
                # into the first's region.
                if (r % 2) != (cid == cids[1]):
                    continue
                off = rt.specs[cids[0]].in_off
            writes.append((r, cid, rng.randn(int(t.in_log[cid])).astype(
                np.float32), off))
    if case == "ragged_zero_chunk":
        assert 0 in rt.specs[cids[0]].chunk_sizes
    path = "gather" if case == "in_perm_all_to_all" else "runs"
    return rt, writes, path


@pytest.mark.parametrize("case", [
    "ceil_chunk_all_reduce", "pad_free_identity", "flat_all_to_all",
    "ragged_zero_chunk", "bf16_heap", "offset_overrides",
    "stacked_unlike_rows", "all_rank_stack", "in_perm_all_to_all"])
def test_write_plan_matches_gather_reference_bitwise(case):
    """Over a polluted heap, a bulk write leaves the heap bit-identical to
    the numpy element gather: live positions copied, every pad of the
    written spans zero, the rest of the heap untouched.  Only the in_perm
    layout takes the gather, with device maps; every other layout packs
    as run copies and uploads none."""
    rt, writes, path = _write_case(case)
    _pollute(rt)
    before = np.asarray(rt.state.heap_in)
    rt.write_inputs_bulk({(r, cid): (data, int(off))
                          for r, cid, data, off in writes})
    got = np.asarray(rt.state.heap_in)
    want = _reference_heap(rt, before, writes)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    (plan,) = rt._staging._write_plans.values()
    assert plan.path == path
    assert (plan.gather_src is None and plan.mask is None) == (
        path != "gather")
    assert rt.stats()["staging_gather_flushes"] == (path == "gather")


def test_grad_sync_write_set_builds_a_run_plan():
    """The grad-sync shape (every rank stages every bucket, uneven chunk
    tails) packs as run copies on every step: no device maps, no write
    through the gather, and the mean is exact."""
    from repro.train.occl_sync import OcclGradSync

    tmpl = {"a": np.zeros((5, 7), np.float32),
            "b": np.zeros(13, np.float32),
            "c": np.zeros((3, 11), np.float32),
            "d": np.zeros(17, np.float32)}
    sync = OcclGradSync(tmpl, n_ranks=2, bucket_elems=40, slice_elems=8)
    sync.occl._ensure_built()
    t = sync.occl._tables
    assert len(sync.buckets) > 1 and any(b.total % 2 for b in sync.buckets)
    assert any(int(t.in_span[b.coll_id]) > b.total for b in sync.buckets)
    rng = np.random.RandomState(3)
    for _ in range(2):
        grads = [{k: rng.randn(*v.shape).astype(np.float32)
                  for k, v in tmpl.items()} for _ in range(2)]
        outs = sync.all_reduce(grads)
        for k in tmpl:
            want = (grads[0][k] + grads[1][k]) / 2
            for r in range(2):
                np.testing.assert_array_equal(np.asarray(outs[r][k]), want)
    plans = list(sync.occl._staging._write_plans.values())
    assert plans and all(p.path == "runs" and p.gather_src is None
                         and p.mask is None for p in plans)
    st = sync.stats()
    assert st["staging_flush_writes"] == 2
    assert st["staging_gather_flushes"] == 0
