"""On-chip benchmark of the FL training path.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` through `repro.fl.run_fl` on the chips
of this machine and prints, as the last line of stdout, one JSON
object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer metrics from a
profiler trace), `device`, with `--trace 1` a `breakdown`, and last
`checks`: each number of the comparison with its limit, which also end
standard error. It exits non-zero, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.

The persistent compilation cache is `$JAX_COMPILATION_CACHE_DIR` where
set, else `<checkout>/.jax_cache`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.core import cell as cellmod
    cell = cellmod.load(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench.core import harness
    out, readings = harness.run(cell, args.seed, args.seconds,
                                bool(args.trace), T_START,
                                devices[:cell.chips])
    for name, v in readings.items():
        print(f"reading {name} {v!r}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
