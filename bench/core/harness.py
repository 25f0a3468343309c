"""One run of one cell through `repro.fl.run_fl`, the entry users call.

Set-up: one short warm-up `run_fl` of a few dispatches and the eval
that ends it, which compiles the cell's shapes (or loads them from the
persistent cache) and times the dispatch pitch. Then the measured
`run_fl` call: one eval period of set-up, then as many whole eval
periods as the pitch says fill `seconds`. Its own host spans
(`spans.py`) give the window; set-up is process start to window start.
With `--trace 1` the call runs one eval period more, and only that
period is profiled (`devtrace.Profile`): the metrics read from spans
come from the untraced window before it, the device's from the trace.

Two things are observed, and nothing of the program is replaced:

* `Capture` wraps the cycle function that `run_fl` builds. Every call
  goes through unchanged; in the first dispatches it copies to the
  host what went in (rows, batches, the plan slices) and the rows that
  came out, which the comparison reads once the window has closed.
* `Kept` records which `TraceRecorder` `run_fl` made, to read its
  spans and its epoch.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import tempfile
import types

import numpy as np

from bench.core import check, devtrace, peaks, reference, spans
from bench.core import plan as plancheck

STEPS = 3      # dispatches the reference follows
WARMUP_DISPATCHES = 3
WARMUP_MIN_ROUNDS = 12


def fl_config(cell, seed: int, rounds: int, trace_path: str | None):
    from repro.fl import FLConfig
    t = cell.traffic
    return FLConfig(
        dataset=cell.config["dataset"], network=t["network"],
        topology=t["topology"], t=t["t"], rounds=rounds,
        local_updates=t["local_updates"], batch_size=t["batch_size"],
        lr=t["lr"], momentum=t["momentum"], seed=seed,
        eval_every=t["eval_every"], samples_per_silo=t["samples_per_silo"],
        alpha=t["alpha"], mesh=t["mesh"], trace=trace_path)


def _host_rows(x, n: int) -> np.ndarray:
    import jax
    return np.asarray(jax.device_get(x))[:n]


@dataclasses.dataclass
class Capture:
    eval_every: int
    wrap: object = None        # wraps each cycle function (faults only)
    at_round: dict = dataclasses.field(default_factory=dict)
    #   rounds done -> called before the dispatch that starts there
    r: int = 0                 # rounds per dispatch
    n: int = 0
    src: np.ndarray = None
    dst: np.ndarray = None
    calls: int = 0
    rounds: int = 0
    rows: dict = dataclasses.field(default_factory=dict)
    feed: list = dataclasses.field(default_factory=list)
    plan: list = dataclasses.field(default_factory=list)

    def snap_rounds(self):
        return {self.r, STEPS * self.r, self.eval_every}


@contextlib.contextmanager
def observe(cap: Capture):
    from repro.fl import runtime
    real = runtime.make_cycle_fn

    def make(rt, **kw):
        fn = real(rt, **kw)
        if cap.wrap is not None:
            fn = cap.wrap(fn, rt)
        cap.r, cap.n = int(rt.num_rounds_cycle), int(rt.num_silos)
        cap.src = np.asarray(rt.src_sorted)
        cap.dst = np.asarray(rt.dst_sorted)
        if cap.eval_every % cap.r or STEPS * cap.r > cap.eval_every:
            raise ValueError(f"eval_every {cap.eval_every} must be a multiple "
                             f"of R={cap.r} and hold {STEPS} dispatches")

        def cycle(state, batches, strong, coeffs, diag):
            if cap.rounds in cap.at_round:
                cap.at_round.pop(cap.rounds)()
            if cap.calls == 0:
                cap.rows[0] = _host_rows(state.w, cap.n)
            if cap.calls < STEPS:
                cap.feed.append((np.asarray(batches["x"]),
                                 np.asarray(batches["y"])))
                cap.plan.append(tuple(np.asarray(a)
                                      for a in (strong, coeffs, diag)))
            cap.calls += 1
            out = fn(state, batches, strong, coeffs, diag)
            cap.rounds += int(strong.shape[0])
            if cap.rounds in cap.snap_rounds():
                cap.rows[cap.rounds] = _host_rows(out[0].w, cap.n)
            return out

        return cycle

    runtime.make_cycle_fn = make
    try:
        yield cap
    finally:
        runtime.make_cycle_fn = real


@contextlib.contextmanager
def recorders(out: list):
    import repro.obs as obs
    real = obs.TraceRecorder

    class Kept(real):
        def __post_init__(self):
            super().__post_init__()
            out.append(self)

    obs.TraceRecorder = Kept
    try:
        yield out
    finally:
        obs.TraceRecorder = real


@dataclasses.dataclass
class Measured:
    """What one measured call left for the metrics and the comparison."""

    result: object             # FLResult
    window: spans.Window       # untraced
    capture: Capture
    rounds: int
    traced: spans.Window | None    # the profiled period (--trace 1)
    ir: dict | None            # its trace IR
    clock: devtrace.Clock | None


def warmup_rounds(cell) -> int:
    """A few whole dispatches of the stated plan, at least
    `WARMUP_MIN_ROUNDS` so that a one-round cycle's pitch is read over
    more than a couple of dispatches."""
    r = cell.plan.get("rounds_per_dispatch", 1)
    return r * max(WARMUP_DISPATCHES, -(-WARMUP_MIN_ROUNDS // r))


def measure(cell, seed: int, seconds: float, trace: bool, tmp: str,
            wrap=None) -> Measured:
    from repro.fl import run_fl
    ee = cell.traffic["eval_every"]
    kept = []
    with recorders(kept):
        run_fl(fl_config(cell, seed, warmup_rounds(cell),
                         f"{tmp}/warmup.json"))
    period = spans.period_estimate(kept[-1].host_events, ee)
    periods = max(1, round(seconds / period))
    rounds = ee * (1 + periods + trace)

    kept, cap = [], Capture(eval_every=ee, wrap=wrap)
    prof = devtrace.Profile(f"{tmp}/profile") if trace else None
    if trace:
        cap.at_round[ee * (1 + periods)] = prof.start
    with recorders(kept), observe(cap):
        try:
            result = run_fl(fl_config(cell, seed, rounds,
                                      f"{tmp}/measured.json"))
        finally:
            if trace:
                prof.stop()
    rec = kept[-1]
    win = spans.window(rec.host_events, rec._epoch, 0, periods)
    traced = ir = clock = None
    if trace:
        traced = spans.window(rec.host_events, rec._epoch, periods,
                              periods + 1)
        ir, clock = prof.ir_and_clock()
    return Measured(result, win, cap, rounds, traced, ir, clock)


def reference_numbers(cell, seed: int, m: Measured) -> tuple[dict, dict]:
    """The comparison's numbers for one measured call, and the pieces the
    readings of control and faults reuse."""
    cfg, t, cap = cell.config, cell.traffic, m.capture
    n, r = cap.n, cap.r
    data = reference.make_data(cfg["synthetic_data"], n, t["samples_per_silo"],
                               t["alpha"], seed)
    x, y = reference.make_feed(data, STEPS * r, t["batch_size"],
                               t["local_updates"], seed)
    w0, layout = reference.initial_row(cell.model, cfg, seed, n)
    w0 = np.asarray(w0)
    px = np.concatenate([f[0] for f in cap.feed])
    py = np.concatenate([f[1] for f in cap.feed])
    plan = reference.Plan(cap.src, cap.dst,
                          *(np.concatenate([p[i] for p in cap.plan])
                            for i in range(3)))
    ref = reference.run_first_steps(
        cell.model, cfg, w0, layout, x, y, plan, lr=t["lr"],
        silo_block=cfg["reference"]["silo_block"],
        snapshot_at=(r, STEPS * r))
    loss1_ref = reference.first_loss(
        cell.model, cfg, w0, layout, x, y, arith=reference.CONFIGURED,
        silo_block=cfg["reference"]["silo_block"])
    ee = cap.eval_every
    mean_rows = np.mean(cap.rows[ee], axis=0, dtype=np.float32)
    acc_ref = reference.accuracy(cell.model, cfg, layout, mean_rows,
                                 data.test_x, data.test_y)
    n_test = len(data.test_y)
    values = {
        "init_bits": _differ(cap.rows[0], np.broadcast_to(w0, (n, w0.size))),
        "feed_bits": _differ(px, x) + _differ(py, y),
        **plancheck.numbers(n, cap.src, cap.dst, cap.plan, t["t"],
                            cell.plan),
        **check.step_numbers(m.result.round_losses, cap.rows, ref, w0,
                             layout, r, loss1_ref),
        "eval_answers": round(abs(m.result.eval_accs[0] - acc_ref) * n_test),
    }
    parts = dict(data=data, x=x, y=y, w0=w0, layout=layout, plan=plan,
                 ref=ref, acc_ref=acc_ref, mean_rows=mean_rows, n_test=n_test,
                 loss1_ref=loss1_ref)
    return values, parts


def _differ(a, b) -> int:
    """Elements that differ; every element where the shapes do."""
    if a.shape != b.shape:
        return max(a.size, b.size)
    return int(np.sum(a != b))


def _memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _context(cell, m: Measured, devices, t_start: float, dw=None):
    """What the metric readers (bench/metrics/<name>.py) read: the
    untraced window; the device's trace of the profiled period, its
    rounds and the peaks only in a traced run."""
    t = cell.traffic
    return types.SimpleNamespace(
        cell=cell, window=m.window, t_start=t_start, device=dw,
        device_rounds=None if m.traced is None else m.traced.rounds,
        chips=len(devices),
        peaks=None if dw is None else peaks.lookup(devices[0].device_kind),
        silos=m.capture.n, edges=len(m.capture.src),
        params=cell.config["params"],
        samples=m.window.rounds * m.capture.n * t["batch_size"]
        * t["local_updates"],
        train_flops_per_sample=6 * cell.model.forward_macs(cell.config))


def _read(entries, ctx) -> dict:
    """Each metric whose reader finds something to read."""
    from bench.core.cell import metric_reader
    out = {}
    for entry in entries:
        v = metric_reader(entry["name"])(ctx)
        if v is not None:
            out[entry["name"]] = {"value": v, "unit": entry["unit"]}
    return out


def _traced(cell, m: Measured, devices, t_start) -> tuple[dict, dict, dict]:
    """(per-layer metrics, device additions, breakdown) of a traced run."""
    tw = m.traced
    dw = devtrace.reduce(m.ir, m.clock.ns(m.clock.perf0),
                         m.clock.ns(tw.t1_s))
    metrics = _read(cell.per_layer, _context(cell, m, devices, t_start, dw))
    host = [(name, m.clock.ns(a), m.clock.ns(b)) for name, a, b in tw.spans]
    breakdown = {"device_ops": devtrace.top_ops(dw),
                 "idle_gaps": devtrace.idle_gaps(dw, host)}
    return metrics, {"busy_s": dw.busy_s, "window_s": dw.window_s}, breakdown


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, wrap=None) -> tuple[dict, dict]:
    """One run: (the contract's result object with `checks` last, every
    number of the comparison, compared or not)."""
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        m = measure(cell, seed, seconds, trace, tmp, wrap=wrap)
        peak = _memory_peak(devices)
        win = m.window
        ee = cell.traffic["eval_every"]
        window_losses = m.result.round_losses[ee:m.rounds]
        failed = int(sum(not math.isfinite(v) for v in window_losses))
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices),
               "memory_peak_bytes": peak}
        out = {"correct": None, "attempted": win.rounds, "failed": failed}
        if trace:
            metrics, extra, breakdown = _traced(cell, m, devices, t_start)
            dev.update(extra)
        else:
            metrics = _read(cell.end_to_end,
                            _context(cell, m, devices, t_start))
            breakdown = None
        m.ir = None
        values, _ = reference_numbers(cell, seed, m)
        ok, compared = check.judge(values, cell.limits)
        out.update(correct=bool(ok and failed == 0 and cell.limits),
                   metrics=metrics, device=dev)
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = compared
        return out, values
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
