"""Benchmark orchestrator — one section per paper table/figure plus the
deliverable reports. Default scale finishes on a CPU container; --full
switches the FCF grid to paper-sized datasets and the full level sweep.

  PYTHONPATH=src python -m benchmarks.run [--full | --dry-run]
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale FCF grid (hours)")
    ap.add_argument("--skip-fcf", action="store_true",
                    help="only the arithmetic/kernel/roofline sections")
    ap.add_argument("--dry-run", action="store_true",
                    help="run every section's dry-run smoke, execute nothing")
    args = ap.parse_args(argv)

    from benchmarks import (async_cohorts, convergence, fault_tolerance,
                            fcf_experiments, kernel_bench, obs_overhead,
                            optimizer_state, payload_compression,
                            payload_table, reduction_sweep, roofline,
                            serving, sharded_rounds, table4)

    t0 = time.time()
    print("=" * 72)
    print("repro benchmarks — FCF-BTS payload optimization (RecSys'21)")
    print("=" * 72)

    if args.dry_run:
        payload_table.main(["--dry-run"])
        kernel_bench.main(["--dry-run"])
        fcf_experiments.main(["--dry-run"])
        reduction_sweep.main(["--dry-run"])
        table4.main(["--dry-run"])
        convergence.main(["--dry-run"])
        payload_compression.main(["--dry-run"])
        sharded_rounds.main(["--dry-run"])
        async_cohorts.main(["--dry-run"])
        fault_tolerance.main(["--dry-run"])
        optimizer_state.main(["--dry-run"])
        serving.main(["--dry-run"])
        obs_overhead.main(["--dry-run"])
        roofline.main(["--dry-run"])
        print(f"\n[dry-run] all sections smoke-checked in "
              f"{time.time() - t0:.1f}s")
        return

    payload_table.run()
    kernel_bench.run()

    if not args.skip_fcf:
        scale = fcf_experiments.FULL if args.full else fcf_experiments.QUICK
        levels = (reduction_sweep.PAPER_LEVELS if args.full
                  else reduction_sweep.QUICK_LEVELS)
        reduction_sweep.run(scale, levels)
        table4.run(scale)
        convergence.run(scale)
        if args.full:
            # full scale regenerates the committed Pareto artifact
            payload_compression.run()
        else:
            # default CPU scale: smaller grid, don't clobber the artifact
            payload_compression.run(rounds=60, theta=30, keeps=(0.10,),
                                    time_rounds=20, out_path=None)

    # sharded engine scaling: spawns fake-CPU-device workers, CPU-only by
    # construction (JAX_PLATFORMS=cpu) — this parent has already imported
    # JAX, and on a TPU host it holds the chip
    sharded_rounds.run(quick=not args.full)

    if args.full:
        # full scale regenerates the committed staleness-curve artifact
        async_cohorts.run()
    else:
        async_cohorts.run_quick()

    # fault tolerance: quality under dropout, corruption pricing, resume
    if args.full:
        fault_tolerance.run()     # regenerates BENCH_fault_tolerance.json
    else:
        fault_tolerance.run_quick()

    # optimizer-state compression: resident footprint, throughput, parity
    if args.full:
        optimizer_state.run()     # regenerates BENCH_optimizer_state.json
    else:
        optimizer_state.run_quick()

    # serving read path: fused compressed scoring vs the dense baseline
    if args.full:
        serving.run()                     # regenerates BENCH_serving.json
    else:
        serving.run(item_scales=(8192,), batches=(8, 64), iters=5,
                    out_path=None)

    # in-loop telemetry cost: enabled-vs-disabled scan engine throughput
    obs_overhead.run(quick=not args.full)

    roofline.run(mesh="pod16x16")
    roofline.run(mesh="pod2x16x16")

    print(f"\ntotal benchmark wall time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
