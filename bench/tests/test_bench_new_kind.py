"""A cell of a new traffic kind on four chips joins the benchmark by new
files plus new manifest entries alone.

The fixtures in ``new_kind/`` are such a cell: a configuration, a traffic
mix of kind ``probe`` on 4 chips, its generator (a ``psum`` over the four
devices, checked against numpy's sum, its control the same sum in bf16)
and its metrics.  In a copy of the benchmark the fixtures are dropped in
place and their entries appended to the manifest; the benchmark's own
tests then pass for the new cell: the manifest's, the CPU rehearsal of
its run and the control of ``correct``.  No file of the benchmark
changes on the way.
"""
import filecmp
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

from bench.tests._run import ROOT

FIXTURES = pathlib.Path(__file__).resolve().parent / "new_kind"
IGNORE = shutil.ignore_patterns("__pycache__", "*.pyc")
# Each test file of the copy, the tests selected in it, and how many pass.
SUITES = [(["test_bench_manifest.py"], None),
          (["test_bench_rehearse.py", "-k", "probe"], 2),
          (["test_bench_control.py", "-k", "probe"], 1)]


def pytest_in(root, args):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", f"bench/tests/{args[0]}", *args[1:]],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def bench_files(root) -> set:
    return {p.relative_to(root) for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_kind_joins_by_new_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=IGNORE)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    added = set()
    for part in ("configs", "traffic", "gens", "metrics"):
        for f in (FIXTURES / part).iterdir():
            dest = tmp_path / "bench" / part / f.name
            assert not dest.exists(), dest
            shutil.copy(f, dest)
            added.add(dest.relative_to(tmp_path))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = json.loads((FIXTURES / "entries.json").read_text())
    for group, items in entries.items():
        manifest[group] = manifest[group] + items
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))

    for args, want in SUITES:
        p = pytest_in(tmp_path, args)
        assert p.returncode == 0, (args, p.stdout[-3000:], p.stderr[-3000:])
        passed = int(re.search(r"(\d+) passed", p.stdout).group(1))
        assert want is None or passed == want, (args, p.stdout[-3000:])

    # Every file of the benchmark is as it was; the fixtures are the only
    # files added, and the manifest only gained the fixtures' entries.
    assert bench_files(tmp_path) == bench_files(ROOT) | added
    for rel in bench_files(ROOT):
        assert filecmp.cmp(ROOT / rel, tmp_path / rel, shallow=False), rel
    copied = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for group, items in entries.items():
        copied[group] = [x for x in copied[group] if x not in items]
    assert copied == json.loads((ROOT / "BENCHMARK.json").read_text())
