"""Unified observability layer (DESIGN.md §17).

Three pieces, one contract:

  * `obs.metrics`  — `MetricsSpec`: per-round device-side scalars
    accumulated INSIDE the jitted whole-cycle `lax.scan` of
    `fl/runtime.py` / `fl/mesh.py` (no host callbacks in the hot path,
    one extra `(R, K)` cycle output). `metrics=None` compiles the
    exact current program — provably inert.
  * `obs.trace`    — `TraceRecorder`: fuses three clocks (simulated
    time from `TimingPlan`/`FaultedSession`, host wall clock around
    the trainer's host phases, controller events) into one ordered
    event log keyed on (round, silo). Host spans nest (each names its
    `parent`) and are also `jax.profiler` annotations, so a profile
    shows them on the device ops' clock; the compiled cycle's ops
    carry the `fl.local_sgd` / `fl.refresh` / `fl.aggregate` scopes.
  * `obs.export`   — Chrome/Perfetto `trace_event` JSON + JSONL
    run-record, consumed by `benchmarks/obs_bench.py` and
    `python -m repro.obs`.
"""

from repro.obs.metrics import MetricsSpec, assemble_row, metric_columns
from repro.obs.trace import TraceRecorder
from repro.obs.export import (to_trace_json, validate_trace,
                              write_trace, write_run_record,
                              load_run_record)

__all__ = [
    "MetricsSpec", "assemble_row", "metric_columns", "TraceRecorder",
    "to_trace_json", "validate_trace", "write_trace",
    "write_run_record", "load_run_record",
]
