"""Run one cell of the chip benchmark once.

    python bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>
    python bench/run_cell.py --workload <name> --rehearse   # CPU, tiny

Everything comes from the seed and is made on the device; set-up (build,
warm-up, compilation) is timed as ``setup_s``, then the cell's window
runs for ``--seconds`` (``--trace 1``: the traffic's ``trace_units`` under
the profiler).  After the window the outputs are compared with the plain
reference, each number compared is printed beside its limit as the last
lines on stderr, and the last line on stdout is the result.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.  ``--rehearse`` runs tiny sizes on the CPU and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench.peaks import peaks  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no result line")
    return ap.parse_args(argv)


def prepare(args):
    """Manifest entries, the cell's data files and its generator."""
    manifest = H.load_manifest()
    cell = H.cell_entry(manifest, args.workload)
    cfg_entry = H.config_entry(manifest, cell["config"])
    config = H.load_json(H.ROOT / cfg_entry["file"])
    traffic = H.load_json(H.traffic_path(cell["traffic"]))
    return manifest, cell, config, traffic


def run(args, out=sys.stdout) -> int:
    manifest, cell, config, traffic = prepare(args)
    chips = cell["chips"]
    if args.rehearse and chips > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count"
                                   f"={chips}")
    if not args.rehearse:
        cache = H.enable_compile_cache()
    clock = H.CompileClock()
    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    dev = H.device_info()
    H.log(f"device: {dev}")
    if not args.rehearse:
        if dev["platform"] != "tpu" or dev["count"] < chips:
            H.log(f"run_cell: needs {chips} TPU chip(s), found {dev}; "
                  "there is no CPU fallback")
            return 2
        H.log(f"compile cache: {cache}")
    devices = jax.devices()[:chips]
    gen = H.generator(traffic["kind"])
    spans = H.Spans()
    ctx = {"config": config, "traffic": traffic, "seed": args.seed,
           "seed_words": H.seeds(args.seed), "rehearse": args.rehearse,
           "spans": spans, "chips": chips}
    job = gen.setup(ctx)
    setup_s = time.perf_counter() - T_START
    before = clock.snapshot()
    H.log(f"setup_s {setup_s:.3f}; compile so far {before}")

    summary = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        with spans("window"):
            win = gen.window(job, units=traffic["trace_units"])
        jax.profiler.stop_trace()
    else:
        with spans("window"):
            win = gen.window(job, seconds=args.seconds)
    after = clock.snapshot()
    compiles = after["hits"] + after["misses"] - before["hits"] \
        - before["misses"]
    H.log(f"window: {win['units']} units in {win['window_s']:.3f} s; "
          f"compilations inside the window: {compiles}")
    peak = H.peak_bytes(devices)
    counters = gen.counters(job, win)
    work = gen.work(job)
    if args.trace:
        from bench import trace as T
        summary = T.summarize(T.load(TRACE_DIR))
    checks, info = gen.check(job)
    H.log(f"reference: {info if isinstance(info, dict) else 'done'}")
    correct = win["failed"] == 0 and all(c["ok"] for c in checks)

    mctx = {"win": win, "counters": counters, "work": work,
            "trace": summary, "peak_bytes": peak,
            "setup_s": setup_s, "spans": spans, "chips": chips,
            "peaks": None if args.rehearse else peaks(dev["kind"])}
    group = "per_layer" if args.trace else "end_to_end"
    metrics = H.metric_values(H.cell_metrics(manifest, cell["name"], group),
                              mctx) if win["units"] else {}
    H.log(f"metrics: {metrics}")
    if args.trace:
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    dev["memory_peak_bytes"] = peak
    H.print_checks(checks)
    if args.rehearse:
        H.log(f"rehearsal {'passed' if correct else 'FAILED'} at tiny "
              f"sizes on {dev['platform']}: not a chip result")
        return 0 if correct else 1
    breakdown = None if summary is None else {
        "device_ops": summary["top_ops"], "idle_gaps": summary["idle_gaps"]}
    print(H.result_line(correct=correct,
                        attempted=win["units"] + win["failed"],
                        failed=win["failed"], metrics=metrics, device=dev,
                        checks=checks, breakdown=breakdown),
          file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run(parse()))
