"""Readings that the limits of `correct` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--faults 1]

For each seed, in one process: one run of the cell as `run.py` makes it
(a short window, the same set-up and the same first dispatches), its
comparison's numbers against the reference (the lower readings), and
with `--faults 1` the same numbers for

    control        the reference put in the program's place, computed
                   in bfloat16 (the nearest precision below the
                   configuration's float32)
    half_batch     the reference with the second half of every batch
                   left out, the loss the mean over the rest
    no_exchange    (mesh cells) the reference with every buffer whose
                   source lies on another chip never refreshed
    eval_stale     the eval of the starting rows in place of the trained
                   ones (an answer produced from the wrong state)

A state left unchanged reads 1 on both change gaps by their measure
and needs no run. One JSON line per seed goes to stdout and to
`--out`. The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def fault_readings(cell, m, parts) -> dict:
    import numpy as np

    from bench.core import check, harness, reference
    cfg, t, cap = cell.config, cell.traffic, m.capture
    r = cap.r
    common = dict(lr=t["lr"], silo_block=cfg["reference"]["silo_block"],
                  snapshot_at=(r, harness.STEPS * r))
    args = (cell.model, cfg, parts["w0"], parts["layout"], parts["x"],
            parts["y"], parts["plan"])

    def numbers(traj):
        out = check.step_numbers(traj.losses, traj.snapshots, parts["ref"],
                                 parts["w0"], parts["layout"], r,
                                 parts["loss1_ref"])
        out["losses"] = traj.losses
        return out

    def answers(acc):
        return round(abs(acc - parts["acc_ref"]) * parts["n_test"])

    out = {"control": numbers(reference.run_first_steps(
        *args, arith=reference.BF16, **common))}
    out["control"]["eval_answers"] = answers(reference.accuracy(
        cell.model, cfg, parts["layout"], parts["mean_rows"],
        parts["data"].test_x, parts["data"].test_y, arith=reference.BF16))
    out["half_batch"] = numbers(reference.run_first_steps(
        *args, half_batch=True, **common))
    if cell.chips > 1:
        per = -(-cap.n // cell.chips)
        frozen = (cap.src // per) != (cap.dst // per)
        out["no_exchange"] = numbers(reference.run_first_steps(
            *args, frozen_edges=frozen, **common))
    out["eval_stale"] = {"eval_answers": answers(reference.accuracy(
        cell.model, cfg, parts["layout"], parts["w0"],
        parts["data"].test_x, parts["data"].test_y))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--faults", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench.core import cell as cellmod
    from bench.core import harness
    from repro.launch.compile_cache import enable_compile_cache
    cell = cellmod.load(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        tmp = tempfile.mkdtemp(prefix="bench-")
        try:
            t0 = time.perf_counter()
            m = harness.measure(cell, seed, args.seconds, False, tmp)
            t1 = time.perf_counter()
            values, parts = harness.reference_numbers(cell, seed, m)
            t2 = time.perf_counter()
            row = {"workload": cell.name, "seed": seed, "program": values,
                   "round_ms": m.window.seconds / m.window.rounds * 1e3,
                   "measure_s": t1 - t0, "reference_s": t2 - t1}
            if args.faults:
                row.update(fault_readings(cell, m, parts))
            row["losses"] = m.result.round_losses[:harness.STEPS
                                                  * m.capture.r]
            row["ref_losses"] = parts["ref"].losses
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
