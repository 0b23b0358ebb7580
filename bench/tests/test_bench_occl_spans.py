"""The readers of the program's occl.* spans, on a hand-built trace with
known answers, and on the CPU rehearsal's own trace."""
import ast
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness as H
from bench.metrics import _occl_spans as S
from bench.tests._run import ROOT

MS = 1_000_000  # ns
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def run(args, root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + [str(a) for a in args],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=600)


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


# Window 0-100 ms holding two steps of host phases; one pack and one plan
# build lie before the window, and the last launch is clipped to 96-100.
# Device 0 is busy 22-55 and 85-90 ms, so its idle gaps are 0-22
# (midpoint 11, in submit), 55-85 (midpoint 70, where read ends and
# unpack starts: unpack) and 90-100 (midpoint 95, in no phase): 52 of 62
# idle ms lie under a phase span.  Device 1 is not the first device and
# does not count.
EVENTS = [
    ev(HOST, "python", "bench.window", 0, 100),
    ev(HOST, "python", "bench.sync", 5, 75),
    ev(HOST, "python", "occl.pack", -10, 5),
    ev(HOST, "python", "occl.plan_build", -4, 1),
    ev(HOST, "python", "occl.pack", 5, 5),
    ev(HOST, "python", "occl.submit", 10, 2),
    ev(HOST, "python", "occl.pack", 12, 3),
    ev(HOST, "python", "occl.submit", 15, 1),
    ev(HOST, "python", "occl.flush", 16, 4),
    ev(HOST, "python", "occl.plan_build", 17, 1),
    ev(HOST, "python", "occl.launch", 20, 40),
    ev(HOST, "python", "occl.read", 60, 10),
    ev(HOST, "python", "occl.unpack", 70, 10),
    ev(HOST, "python", "occl.launch", 96, 10),
    ev(D0, "XLA Modules", "jit_daemon(1)", 22, 33),
    ev(D0, "XLA Ops", "fusion.1", 22, 20),
    ev(D0, "XLA Ops", "while.2", 40, 15),
    ev(D0, "XLA Ops", "fusion.3", 85, 5),
    ev(D1, "XLA Ops", "fusion.1", 0, 100),
]

# Per step of the two traced steps (seconds), or the share in %.
WANT = {"pack_s.train": 0.004, "submit_s.train": 0.0015,
        "flush_s.train": 0.002, "launch_s.train": 0.022,
        "read_s.train": 0.005, "unpack_s.train": 0.005,
        "plan_builds.train": 0.5,
        "idle_spanned_share.train": 100.0 * 52 / 62}
CTX = {"trace": {"window_s": 0.1}, "win": {"units": 2}}


@pytest.fixture
def trace(monkeypatch):
    def use(events):
        monkeypatch.setattr(S, "_load", lambda: events)
        S.reduced.cache_clear()
    yield use
    S.reduced.cache_clear()


def test_every_reader_is_in_the_manifest():
    names = {m["name"] for m in H.load_manifest()["per_layer"]}
    assert set(WANT) <= names


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_known_answer(trace, name):
    trace(EVENTS)
    assert H.metric_reader(name).read(CTX) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_none_without_occl_spans(trace, name):
    """A program without the spans (or an untraced run) reads nothing."""
    trace([e for e in EVENTS if not e[2].startswith("occl.")])
    assert H.metric_reader(name).read(CTX) is None
    assert H.metric_reader(name).read(dict(CTX, trace=None)) is None


def test_no_device_plane_leaves_only_the_share_out(trace):
    trace([e for e in EVENTS if not e[0].startswith("/device:")])
    r = S.reduced()
    assert r["idle_spanned_share"] is None
    assert r["launch"] == pytest.approx(0.044)


def test_rehearsal_reports_the_span_metrics(tmp_path):
    """The CPU rehearsal's trace has the program's spans: every span reader
    returns a number there, the six phases lie inside the sync span, and
    no staging plan is built inside the window.  The idle share needs a
    device plane, which a CPU trace lacks.  The run is made in a copy of
    the tree, so that its trace directory is its own."""
    for part in ("bench", "src"):
        shutil.copytree(ROOT / part, tmp_path / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = run(["bench/run_cell.py", "--workload", "qwen3-dp2-gradsync",
             "--rehearse", "--seconds", "1", "--trace", "1",
             "--seed", 2**33 + 7], root=tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    line, = [ln for ln in p.stderr.splitlines()
             if ln.startswith("metrics: ")]
    got = {k: v["value"]
           for k, v in ast.literal_eval(line[len("metrics: "):]).items()}
    assert set(WANT) - set(got) == {"idle_spanned_share.train"}
    phases = sum(got[f"{p}_s.train"] for p in S.PHASES)
    assert 0 < phases <= 1.01 * got["sync_s.train"]
    assert got["plan_builds.train"] == 0
