"""The occl.* spans of the grad-sync hot path, read back from a profiler
trace: one span per phase where the work happens, siblings that never
overlap, and stats that agree with the runtime's own counters."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.occl_sync import OcclGradSync, static_all_reduce

RANKS = 2
PHASES = ("pack", "submit", "flush", "launch", "read", "unpack")
SHAPES = {"a": (8, 16), "b": (40,), "c": (4, 4, 6), "d": (30,)}


def _grads(seed):
    key = jax.random.PRNGKey(seed)
    return {k: jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32)
            for i, (k, s) in enumerate(sorted(SHAPES.items()))}


def _events(trace_dir):
    from jax.profiler import ProfileData

    f = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(f))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(("occl.", "test.step"))]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two all_reduce steps under the profiler, each inside a test.step
    span, with the runtime's counters before and after each."""
    tmpl = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), _grads(0))
    sync = OcclGradSync(tmpl, RANKS, bucket_elems=200, slice_elems=64)
    assert len(sync.buckets) >= 2
    inputs = [[_grads(10 * s + r) for r in range(RANKS)] for s in range(2)]
    outs, counters = [], [sync.stats()]
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        for s in range(2):
            with jax.profiler.TraceAnnotation(f"test.step{s}"):
                outs.append(sync.all_reduce(inputs[s]))
            counters.append(sync.stats())
    events = _events(trace_dir)
    steps = []
    for s in range(2):
        (lo, hi), = [(a, b) for n, a, b, _ in events if n == f"test.step{s}"]
        steps.append([e for e in events
                      if e[0].startswith("occl.") and lo <= e[1] < hi])
    return {"sync": sync, "inputs": inputs, "outs": outs,
            "counters": counters, "steps": steps}


def _named(step_events, phase):
    return [e for e in step_events if e[0] == f"occl.{phase}"]


def _delta(traced, step, key):
    return traced["counters"][step + 1][key] - traced["counters"][step][key]


@pytest.mark.parametrize("step", [0, 1])
@pytest.mark.parametrize("phase", PHASES)
def test_one_span_per_phase_call(traced, phase, step):
    per_bucket = len(traced["sync"].buckets) * RANKS
    want = {"pack": per_bucket, "submit": per_bucket, "flush": 1,
            "launch": _delta(traced, step, "launches"), "read": 1,
            "unpack": 1}[phase]
    assert want >= 1
    assert len(_named(traced["steps"][step], phase)) == want


def test_phases_are_siblings(traced):
    """The six phases never overlap; plan builds nest in a flush or a
    read."""
    spans = sorted((a, b, n) for step in traced["steps"]
                   for n, a, b, _ in step if n != "occl.plan_build")
    assert {n for _, _, n in spans} == {f"occl.{p}" for p in PHASES}
    for (_, end, n), (start, _, m) in zip(spans, spans[1:]):
        assert end <= start, (n, m)
    for step in traced["steps"]:
        for _, a, b, _ in _named(step, "plan_build"):
            assert any(n in ("occl.flush", "occl.read") and s <= a and b <= e
                       for n, s, e, _ in step)


@pytest.mark.parametrize("step", [0, 1])
@pytest.mark.parametrize("phase", ["pack", "submit", "flush"])
def test_staged_bytes_agree(traced, phase, step):
    """Every byte packed is submitted and flushed: the spans' bytes sum
    to the step's rise in the runtime's staging_flush_bytes."""
    got = sum(st["bytes"] for *_, st in _named(traced["steps"][step], phase))
    assert got == _delta(traced, step, "staging_flush_bytes") > 0


@pytest.mark.parametrize("step", [0, 1])
def test_read_and_unpack_bytes(traced, step):
    sync = traced["sync"]
    want = 4 * RANKS * sum(b.total for b in sync.buckets)
    for phase in ("read", "unpack"):
        (ev,) = _named(traced["steps"][step], phase)
        assert ev[3]["bytes"] == want


def test_plan_build_in_first_step_only(traced):
    first, second = (_named(s, "plan_build") for s in traced["steps"])
    assert len(first) >= 1 and second == []
    assert _delta(traced, 0, "plan_builds") == len(first)
    assert _delta(traced, 1, "plan_builds") == 0
    assert {ev[3]["kind"] for ev in first} <= {"write", "read"}


@pytest.mark.parametrize("step", [0, 1])
def test_spans_leave_results_unchanged(traced, step):
    want = static_all_reduce(traced["inputs"][step])
    for r in range(RANKS):
        for a, b in zip(jax.tree_util.tree_leaves(traced["outs"][step][r]),
                        jax.tree_util.tree_leaves(want[r])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
