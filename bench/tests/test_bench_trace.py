"""The trace reduction on a hand-built trace with known answers."""
import pytest

from bench import trace as T

MS = 1_000_000  # ns


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"

# Window 0-100 ms.  Device 0: module daemon 10-40 ms with two ops (one
# collective-permute), module fn 50-60 ms with one op overlapping the
# window's end on nothing else; an op before the window is clipped away.
# Device 1: one op 0-50 ms in module daemon.
EVENTS = [
    ev(HOST, "python", "bench.window", 0, 100),
    ev(HOST, "python", "bench.sync", 5, 60),
    ev(HOST, "python", "bench.read", 40, 10),
    ev(HOST, "python", "unrelated", 0, 100),
    ev(D0, "XLA Modules", "jit_daemon(123)", 10, 30),
    ev(D0, "XLA Ops", "fusion.1", 10, 10),
    ev(D0, "XLA Ops", "%collective-permute-start.2", 15, 20),
    ev(D0, "XLA Modules", "jit_fn(7)", 50, 10),
    ev(D0, "XLA Ops", "gather.3 = f32[8]{0} gather(f32[9]{0} %a, s32[8] %b)",
       50, 10),
    ev(D0, "XLA Ops", "early", -20, 10),
    ev(D1, "XLA Modules", "jit_daemon(9)", 0, 50),
    ev(D1, "XLA Ops", "fusion.1", 0, 50),
]


def test_union_and_gaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    assert T.gaps([[2, 3], [5, 9]], 0, 10) == [(0, 2), (3, 5), (9, 10)]


def test_summary_known_answers():
    s = T.summarize(EVENTS)
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(0.100)
    # device 0 busy 10-35 and 50-60 = 35 ms; device 1 busy 50 ms; mean 42.5
    assert s["busy_s"] == pytest.approx(0.0425)
    # module time, mean over the two devices: daemon (30 + 50) / 2, fn 10 / 2
    assert s["module_s"]["daemon"] == pytest.approx(0.040)
    assert s["module_s"]["fn"] == pytest.approx(0.005)
    ops = s["module_op_s"]["daemon"]
    assert ops["collective-permute-start.2"] == pytest.approx(0.010)
    assert ops["fusion.1"] == pytest.approx(0.030)
    assert s["top_ops"][0] == ["daemon/fusion.1", pytest.approx(0.030)]
    assert s["module_op_s"]["fn"] == {"gather.3": pytest.approx(0.005)}
    # device 0's idle gaps: 0-10 (sync from 5 ms, midpoint 5 -> sync),
    # 35-50 (read holds 40-50, midpoint 42.5 -> read), 60-100 (none).
    assert s["idle_gaps"] == [["none", pytest.approx(0.040)],
                              ["read", pytest.approx(0.015)],
                              ["sync", pytest.approx(0.010)]]


def test_names():
    assert T.module_name("jit_sharded_fn(42)") == "sharded_fn"
    assert T.module_name("daemon") == "daemon"
    assert T.op_name("%fusion.3") == "fusion.3"
    assert T.op_name("while.15 = (s32[], f32[2,8]) while(%tuple)") == \
        "while.15"


def test_window_span_required():
    with pytest.raises(ValueError):
        T.summarize([ev(D0, "XLA Ops", "x", 0, 1)])
