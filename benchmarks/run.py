"""Benchmark driver: one module per paper table/figure + kernels +

roofline. Prints ``name,us_per_call,derived`` CSV.

  python -m benchmarks.run               # full (cycle-time tables full
                                         # 6400 rounds; FL tables reduced
                                         # rounds for CPU budget)
  python -m benchmarks.run --quick       # CI-sized
  python -m benchmarks.run --only table1,table3
"""

from __future__ import annotations

import argparse
import sys
import traceback


def _roofline_rows():
    import pathlib

    from repro.launch.roofline import table

    d = pathlib.Path("experiments/dryrun")
    rows = []
    if not d.exists() or not list(d.glob("*.json")):
        return [("roofline/availability", 0.0,
                 "no dry-run artifacts; run python -m repro.launch.dryrun --all")]
    for r in table(d):
        if r.status == "ok":
            rows.append((f"roofline/{r.mesh}/{r.arch}/{r.shape}", 0.0,
                         f"compute_s={r.compute_s:.5f} "
                         f"memory_s={r.memory_s:.5f} "
                         f"collective_s={r.collective_s:.5f} "
                         f"dominant={r.dominant} "
                         f"useful={r.useful_ratio:.2f}"))
        else:
            rows.append((f"roofline/{r.mesh}/{r.arch}/{r.shape}", 0.0,
                         f"{r.status}: {r.note[:60]}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--rounds", type=int, default=0,
                    help="override FL training rounds")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (ablation_noniid, faults_bench, fig5_convergence,
                            kernel_bench, obs_bench, population_bench,
                            serving_bench, sim_bench, table1_cycle_time,
                            table3_isolated, table4_removal,
                            table5_accuracy, table6_tradeoff, tta_bench)

    suites = {
        "table1": lambda: table1_cycle_time.run(quick=args.quick),
        "table3": lambda: table3_isolated.run(quick=args.quick),
        "sim": lambda: sim_bench.run(quick=args.quick),
        "table4": lambda: table4_removal.run(
            num_rounds=args.rounds or (40 if args.quick else 120),
            quick=args.quick),
        "table5": lambda: table5_accuracy.run(
            num_rounds=args.rounds or (40 if args.quick else 150),
            quick=args.quick),
        "table6": lambda: table6_tradeoff.run(
            num_rounds=args.rounds or (40 if args.quick else 120),
            quick=args.quick, train=not args.quick),
        "fig5": lambda: fig5_convergence.run(
            num_rounds=args.rounds or (40 if args.quick else 150),
            quick=args.quick),
        "kernels": lambda: kernel_bench.run(quick=args.quick),
        # time-to-accuracy design loop (merges design/tta_search rows
        # into BENCH_sim.json without clobbering sim_bench's):
        "tta": lambda: tta_bench.run(quick=args.quick),
        # fault-injection scenario matrix, static vs adaptive TTA
        # (merges faults/ rows; writes the matrix artifact under
        # benchmarks/artifacts/):
        "faults": lambda: faults_bench.run(quick=args.quick),
        # device-grid candidate throughput + population-engine gates
        # (merges design/grid_jax and design/population_search rows):
        "population": lambda: population_bench.run(quick=args.quick),
        # observability overhead gate: metrics-on vs off dispatch ratio
        # + the trace artifact CI uploads (merges obs/ rows):
        "obs": lambda: obs_bench.run(quick=args.quick),
        # train->checkpoint->deploy->serve loop: offered-load sweep
        # over the regional fleet (writes BENCH_serving.json):
        "serving": lambda: serving_bench.run(quick=args.quick),
        "roofline": _roofline_rows,
        # beyond-paper ablation; opt-in (adds ~10 min):
        #   python -m benchmarks.run --only noniid
        "noniid": lambda: ablation_noniid.run(quick=args.quick),
    }

    if only:
        unknown = sorted(only - suites.keys())
        if unknown:
            print(f"unknown --only suite(s): {', '.join(unknown)}; "
                  f"valid: {', '.join(sorted(suites))}", file=sys.stderr)
            raise SystemExit(2)

    opt_in = {"noniid"}
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if only and name not in only:
            continue
        if not only and name in opt_in:
            continue
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}")
                sys.stdout.flush()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc(limit=2)!r}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
