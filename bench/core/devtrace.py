"""Reduce a profiler trace of the measured call to device numbers.

`Profile` traces the last eval period of the measured call: it starts
from inside the call, at that period's first dispatch, and stops once
the call has returned, so the periods before it run untraced.

`load` reads the `.xplane.pb` that `jax.profiler` writes into a small
plain structure (the trace "IR"):

    {"annotation": [start_ns, dur_ns],           # ANNOTATION on the host
     "devices": {plane: [[op, start_ns, dur_ns], ...]}}

with one entry per device plane, holding the events of its `XLA Ops`
line, each named by its HLO instruction (`fusion.12`, `while.29`,
`edge_aggregate.10`: the trace gives the whole instruction text). Ops
nest on that line: a `while` spans the ops of its body. Everything
after that works on the IR alone, so the reduction is tested on a
recorded IR without a chip. Times on a device plane and on
the host plane share one clock; a host time from `time.perf_counter`
is put on it through the annotation (`Clock`).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import time

import numpy as np

#: Host span the harness wraps around the measured `run_fl` call.
ANNOTATION = "bench.measured_call"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the Python tracer would slow the loop
    opts.host_tracer_level = 1     # user annotations only
    return opts


class Profile:
    """The profiler over part of a call: `start` (from inside it) opens
    the trace and the `ANNOTATION` span and reads `perf0` just inside
    that span; `stop` closes both."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.perf0 = None
        self._span = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.log_dir,
                                 profiler_options=profile_options())
        self._span = jax.profiler.TraceAnnotation(ANNOTATION)
        self._span.__enter__()
        self.perf0 = time.perf_counter()

    def stop(self):
        if self._span is None:
            return
        import jax
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    def ir_and_clock(self) -> tuple[dict, "Clock"]:
        if self.perf0 is None:
            raise RuntimeError("the profiler never started")
        ir = load(find_xspace(self.log_dir))
        return ir, Clock(self.perf0, ir["annotation"][0])


def find_xspace(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def load(path: str) -> dict:
    """The trace IR of one `.xplane.pb` (see module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ann, devices = None, {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [op_name(ev.name), float(ev.start_ns),
                         float(ev.duration_ns)] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANNOTATION:
                        ann = [float(ev.start_ns), float(ev.duration_ns)]
    if ann is None:
        raise RuntimeError(f"no {ANNOTATION!r} span in {path}")
    return {"annotation": ann, "devices": devices}


def op_name(text: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class Clock:
    """Maps `time.perf_counter()` seconds onto the trace's ns clock:
    `perf0` was read just inside the annotation that starts at `ns0`."""

    perf0: float
    ns0: float

    def ns(self, perf_s: float) -> float:
        return self.ns0 + (perf_s - self.perf0) * 1e9


@dataclasses.dataclass
class DeviceWindow:
    """Device activity inside one window, averaged over the devices."""

    window_s: float
    busy_s: float                 # union of op intervals
    op_seconds: dict              # op name -> self seconds
    op_counts: dict               # op name -> events
    gaps: list                    # (start_ns, end_ns) idle, first device
    num_devices: int


def _clip(events, t0, t1):
    if not events:
        return np.zeros(0), np.zeros(0), []
    names = [e[0] for e in events]
    s = np.array([e[1] for e in events])
    e = s + np.array([e[2] for e in events])
    keep = (e > t0) & (s < t1)
    s, e = np.clip(s[keep], t0, t1), np.clip(e[keep], t0, t1)
    return s, e, [n for n, k in zip(names, keep) if k]


def _self_times(s, e):
    """Each interval's length minus the parts its nested intervals
    cover (a `while` keeps only the time none of its body ops ran)."""
    order = np.lexsort((-(e - s), s))
    self_t = (e - s).astype(float)
    stack = []
    for i in order.tolist():
        while stack and e[stack[-1]] <= s[i]:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= min(e[i], e[stack[-1]]) - s[i]
        stack.append(i)
    return np.maximum(self_t, 0.0)


def _union(s, e, t0, t1):
    """Busy seconds-in-ns and idle gaps of intervals [s, e) in [t0, t1]."""
    if s.size == 0:
        return 0.0, [(t0, t1)]
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    run_end = np.maximum.accumulate(e)
    gaps = []
    if s[0] > t0:
        gaps.append((t0, float(s[0])))
    idle = s[1:] - run_end[:-1]
    for i in np.flatnonzero(idle > 0):
        gaps.append((float(run_end[i]), float(s[i + 1])))
    if run_end[-1] < t1:
        gaps.append((float(run_end[-1]), t1))
    return (t1 - t0) - sum(b - a for a, b in gaps), gaps


def reduce(ir: dict, t0_ns: float, t1_ns: float) -> DeviceWindow:
    """Busy time, per-op time and idle gaps in [t0_ns, t1_ns]."""
    devs = sorted(ir["devices"])
    if not devs:
        raise RuntimeError("the trace holds no device plane")
    busy, ops, counts, gaps0 = 0.0, {}, {}, None
    for d in devs:
        s, e, names = _clip(ir["devices"][d], t0_ns, t1_ns)
        b, gaps = _union(s, e, t0_ns, t1_ns)
        busy += b
        if gaps0 is None:
            gaps0 = gaps
        for n, dur in zip(names, _self_times(s, e).tolist()):
            ops[n] = ops.get(n, 0.0) + dur
            counts[n] = counts.get(n, 0) + 1
    k = len(devs)
    return DeviceWindow(
        window_s=(t1_ns - t0_ns) / 1e9, busy_s=busy / k / 1e9,
        op_seconds={n: v / k / 1e9 for n, v in ops.items()},
        op_counts={n: c / k for n, c in counts.items()},
        gaps=gaps0, num_devices=k)


def top_ops(dw: DeviceWindow, k: int = 10) -> list:
    return [[n, v] for n, v in sorted(dw.op_seconds.items(),
                                      key=lambda kv: -kv[1])[:k]]


def idle_gaps(dw: DeviceWindow, host_spans: list, k: int = 10) -> list:
    """The k longest idle gaps of the first device, each named by the
    host span (name, start_ns, end_ns) that holds its midpoint, or
    `host_loop` where the host was between spans."""
    out = []
    for a, b in sorted(dw.gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) / 2
        name = next((n for n, s, e in host_spans if s <= mid < e),
                    "host_loop")
        out.append([name, (b - a) / 1e9])
    return out
