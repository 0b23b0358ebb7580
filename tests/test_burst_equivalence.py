"""Slice-burst execution is a pure schedule transformation: outputs at
``burst_slices > 1`` are BIT-IDENTICAL to the seed single-slice semantics
(``burst_slices = 1``) for every collective kind, group size and order
policy, including the adversarial-order workloads that deadlock a
statically-sequenced baseline.

Each slice's value is the same pure function of the same operands in the
same order regardless of how many slices ride one superstep, so equality
is exact (assert_array_equal), not approximate.
"""
import numpy as np
import pytest

from repro.core import CollKind, OcclConfig, OcclRuntime, OrderPolicy

# These configs use shallow connectors ON PURPOSE (the credit-return
# equilibrium is part of the semantics under test, not a perf target).
pytestmark = pytest.mark.filterwarnings(
    "ignore::repro.core.runtime.ConnDepthWarning")

KINDS = [CollKind.ALL_REDUCE, CollKind.ALL_GATHER, CollKind.REDUCE_SCATTER,
         CollKind.BROADCAST, CollKind.REDUCE]
GROUP_SIZES = [1, 2, 4]
R = 4


def _run_all_kinds(policy: OrderPolicy, burst: int):
    """One runtime hosting every (kind, group_size) pair; adversarial
    per-rank submission orders.  Returns {(kind, gs): {rank: output}}."""
    cfg = OcclConfig(
        n_ranks=R, max_colls=16, max_comms=len(GROUP_SIZES), slice_elems=4,
        conn_depth=5, heap_elems=1 << 14, order_policy=policy,
        burst_slices=burst, superstep_budget=1 << 14)
    rt = OcclRuntime(cfg)
    comms = {gs: rt.communicator(list(range(gs))) for gs in GROUP_SIZES}
    rng = np.random.RandomState(7)
    ids, inputs = {}, {}
    for gs in GROUP_SIZES:
        for kind in KINDS:
            n_elems = int(rng.randint(1, 40))
            cid = rt.register(kind, comms[gs], n_elems=n_elems, root=0)
            ids[(kind, gs)] = cid
            if kind == CollKind.ALL_GATHER:
                chunk = -(-n_elems // gs)
                inputs[cid] = [rng.randn(chunk).astype(np.float32)
                               for _ in range(gs)]
            else:
                inputs[cid] = [rng.randn(n_elems).astype(np.float32)
                               for _ in range(gs)]
    order = list(ids.values())
    for r in range(R):
        rng_r = np.random.RandomState(100 + r)
        for cid in [order[i] for i in rng_r.permutation(len(order))]:
            kind, gs = next(k for k, v in ids.items() if v == cid)
            if r >= gs:
                continue
            if kind == CollKind.BROADCAST:
                if r == 0:
                    rt.write_input(r, cid, inputs[cid][0])
            else:
                rt.write_input(r, cid, inputs[cid][r])
            rt.submit(r, cid)
    rt.drive(max_launches=128)
    return {
        key: {r: rt.read_output(r, cid) for r in range(key[1])}
        for key, cid in ids.items()
    }


@pytest.mark.parametrize("policy", [OrderPolicy.FIFO, OrderPolicy.PRIORITY])
@pytest.mark.parametrize("burst", [4, 8])
def test_burst_outputs_bit_identical_to_single_slice(policy, burst):
    base = _run_all_kinds(policy, burst=1)
    got = _run_all_kinds(policy, burst=burst)
    for key in base:
        for r in base[key]:
            np.testing.assert_array_equal(
                base[key][r], got[key][r],
                err_msg=f"kind={key[0].name} gs={key[1]} rank={r} "
                        f"policy={policy.name} burst={burst}")


def test_pallas_burst_path_end_to_end():
    """use_pallas=True routes the whole [L*B, SLICE] superstep burst
    through one fused_primitive_batch call; outputs must match the
    jnp reference path exactly (both compute in f32).  Off the TPU the
    kernel runs in the Pallas interpreter, asked for by name."""
    outs = {}
    for use_pallas in (False, True):
        cfg = OcclConfig(n_ranks=2, max_colls=4, max_comms=1, slice_elems=8,
                         conn_depth=6, burst_slices=4, heap_elems=1 << 13,
                         use_pallas=use_pallas, pallas_interpret=use_pallas,
                         superstep_budget=1 << 13)
        rt = OcclRuntime(cfg)
        comm = rt.communicator([0, 1])
        cid = rt.register(CollKind.ALL_REDUCE, comm, n_elems=96)
        rng = np.random.RandomState(11)
        xs = [rng.randn(96).astype(np.float32) for _ in range(2)]
        for r in range(2):
            rt.submit(r, cid, data=xs[r])
        rt.drive()
        outs[use_pallas] = [rt.read_output(r, cid) for r in range(2)]
        for r in range(2):
            np.testing.assert_allclose(outs[use_pallas][r], sum(xs),
                                       rtol=1e-4)
    for r in range(2):
        np.testing.assert_array_equal(outs[False][r], outs[True][r])


def _run_adversarial(burst: int):
    """The Sec. 5.2 headline workload (examples/adversarial_orders.py):
    8 ranks submit 8 all-reduces in pairwise-different orders."""
    Radv, C = 8, 8
    rng = np.random.RandomState(42)
    orders = {r: list(rng.permutation(C)) for r in range(Radv)}
    cfg = OcclConfig(n_ranks=Radv, max_colls=C, max_comms=1, slice_elems=8,
                     conn_depth=4, burst_slices=burst, heap_elems=1 << 15,
                     superstep_budget=1 << 15)
    rt = OcclRuntime(cfg)
    world = rt.communicator(list(range(Radv)))
    sizes = [32 << (i % 3) for i in range(C)]
    ids = [rt.register(CollKind.ALL_REDUCE, world, n_elems=s) for s in sizes]
    data = {i: [rng.randn(sizes[i]).astype(np.float32) for _ in range(Radv)]
            for i in range(C)}
    for r in range(Radv):
        for slot in orders[r]:
            rt.submit(r, ids[slot], data=data[slot][r])
    rt.drive(max_launches=128)          # convergence == deadlock freedom
    return {i: {r: rt.read_output(r, ids[i]) for r in range(Radv)}
            for i in range(C)}


def test_burst_adversarial_orders_bit_identical():
    base = _run_adversarial(burst=1)
    got = _run_adversarial(burst=4)
    for i in base:
        for r in base[i]:
            np.testing.assert_array_equal(base[i][r], got[i][r],
                                          err_msg=f"coll={i} rank={r}")
