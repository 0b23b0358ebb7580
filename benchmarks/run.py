"""Benchmark suite entrypoint: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (common.row).
  Fig. 5  -> bench_overheads       Fig. 6/7 -> bench_collectives
  Sec 5.2 -> bench_deadlock        Fig. 8/10 -> bench_training
  Fig. 9  -> bench_gang

``--quick`` runs a CI-sized smoke (small sizes, 1 iter) that still
rewrites BENCH_collectives.json — the burst sweep, the adversarial
contention sweep, the staging record, the mesh fast-path record and the
training overlap record — so the perf record stays reproducible from a
cold checkout.  Both modes end
with ``bench_collectives.validate_record()``: a stale or partial record
(e.g. a missing ``contention`` section) fails the run loudly instead of
silently passing; section writers replace the file atomically, so a
partial record can never be produced by an interrupted run.
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))


def main(quick: bool = False) -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    import bench_collectives
    if quick:
        bench_collectives.run(sizes=(64,), iters=1)
        bench_collectives.run_burst_sweep(bursts=(1, 8), n=8192, iters=1)
        # Full-size contention sweep even in --quick: the check_gates.py
        # B8 <= 0.5x B1 threshold is calibrated against the n=2048 record
        # (~3x fewer supersteps); the n=1024 smoke sits at ~0.49 — a 2%
        # margin any benign schedule shift would trip.
        bench_collectives.run_contention_sweep(bursts=(1, 8))
        # Staging engine vs the pre-PR bulk/scalar paths at the headline
        # 8-rank / 16k-elem point (CI smoke keeps the full workload: the
        # speedup is the acceptance-tracked number).
        bench_collectives.run_staging_bench(iters=10)
        bench_collectives.run_mesh_bench()
        # Composite layer: flat ring vs two-level chain at R=16 — the
        # full-size point (the hierarchy gate compares supersteps, which
        # are size-stable, so --quick keeps the acceptance workload).
        # iters=3 even in --quick: the skew gate compares WALL-CLOCK, and
        # best-of-1 timings jitter by ~20% — enough to flip near-ties.
        bench_collectives.run_hierarchy_bench(iters=3)
        # Algorithm zoo + cost-model calibration: the per-algorithm sweep
        # at the two crossover-straddling sizes, then the α-β-γ fit +
        # auto-pick record (check_gates asserts auto matches the measured
        # winners).  Full-size points even in --quick: the gates compare
        # measured winners, and smaller payloads move the crossover.
        # iters=3: the pick-vs-best wall tolerance is 1.15x, within
        # single-shot dispatch noise at the small payload.
        bench_collectives.run_algo_sweep(iters=3)
        # All-to-all: flat relay ring vs two-level chain at R=16, plus
        # the adversarial a2a x all-reduce contention scenario — the
        # alltoall supersteps gate compares structural counts, so the
        # full-size point stays in --quick too.
        bench_collectives.run_alltoall_bench(iters=3)
        import calibrate
        calibrate.main()
        # Training overlap record (tick contract): the dense grad-sync
        # and MoE barrier-vs-overlap points are REQUIRED sections — the
        # exposed-superstep gates compare structural counts, so the
        # full-size workload stays in --quick (iters only trims the
        # wall-clock side channel).
        import bench_training
        bench_training.run_training_bench(iters=1)
        # Reliability record: evict-vs-fresh supersteps are structural
        # (same replayed schedule), and the recorder-overhead point uses
        # best-of-N wall timing, so the CI smoke keeps the acceptance
        # workload and only trims iters.
        import bench_reliability
        bench_reliability.run_reliability_bench(iters=5)
        # Serving QoS replay: the p99 gate compares structural superstep
        # percentiles on a deterministic trace, so the CI smoke runs the
        # full acceptance workload (a few thousand 1-superstep ticks).
        import bench_serving
        bench_serving.run_serving_bench()
        # Fail LOUDLY on a stale/partial record: every section the gates
        # consume must have been (re)written by THIS run — a missing
        # ``contention`` key in a stale BENCH_collectives.json used to
        # slip through as a silent no-op.
        bench_collectives.validate_record()
        return
    import bench_overheads
    bench_overheads.run(sizes=(64, 1024, 4096))
    bench_collectives.run(sizes=(64, 4096), iters=2)
    # Machine-readable perf trajectory: supersteps/sec, slices/sec and
    # per-collective latency at burst_slices in {1, 4, 8}, plus the
    # adversarial contention stall/preempt record, written to
    # BENCH_collectives.json at the repo root.
    bench_collectives.run_burst_sweep(iters=2)
    bench_collectives.run_contention_sweep()
    bench_collectives.run_staging_bench(iters=20)
    bench_collectives.run_mesh_bench()
    bench_collectives.run_hierarchy_bench()
    bench_collectives.run_algo_sweep()
    bench_collectives.run_alltoall_bench()
    import calibrate
    calibrate.main()
    import bench_training
    bench_training.run_training_bench()
    import bench_reliability
    bench_reliability.run_reliability_bench()
    import bench_serving
    bench_serving.run_serving_bench()
    bench_collectives.validate_record()
    import bench_deadlock
    bench_deadlock.run(iters=2)
    bench_deadlock.run_a2a_chained(iters=2)
    import bench_gang
    bench_gang.run()
    bench_training.run()


if __name__ == '__main__':
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small sizes, 1 iteration per point")
    main(quick=ap.parse_args().quick)
