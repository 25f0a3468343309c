"""The reduction from traces and spans to the per-layer metrics."""

import json
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest

from bench.core import cell as cellmod
from bench.core import devtrace, harness, peaks, spans

DATA = pathlib.Path(__file__).parent / "data"

# two devices over a 100 ns window [1000, 1100]:
#   dev 0: while.1 [990, 1070) clipped to [1000, 1070), holding
#          edge_aggregate [1020, 1050) and a collective [1050, 1060)
#          -> busy 70, while self time 30, gap [1070, 1100)
#   dev 1: edge_aggregate [1000, 1030) -> busy 30
SMALL_IR = {
    "annotation": [900.0, 400.0],
    "devices": {
        "/device:TPU:0": [["while.1", 990.0, 80.0],
                          ["edge_aggregate.10", 1020.0, 30.0],
                          ["collective-permute-start.3", 1050.0, 10.0],
                          ["fusion.1", 1200.0, 50.0]],
        "/device:TPU:1": [["edge_aggregate.10", 1000.0, 30.0]],
    },
}


def test_reduce_small_trace():
    dw = devtrace.reduce(SMALL_IR, 1000.0, 1100.0)
    assert dw.num_devices == 2
    assert dw.window_s == pytest.approx(100e-9)
    assert dw.busy_s == pytest.approx((70 + 30) / 2 * 1e-9)
    assert dw.op_seconds["edge_aggregate.10"] == pytest.approx(30e-9)
    assert dw.op_counts["edge_aggregate.10"] == 1.0
    assert dw.op_seconds["while.1"] == pytest.approx(15e-9)
    assert "fusion.1" not in dw.op_seconds
    assert dw.gaps == [(1070.0, 1100.0)]
    host = [("dispatch", 1000.0, 1050.0), ("eval", 1050.0, 1090.0)]
    assert devtrace.idle_gaps(dw, host) == [["eval", pytest.approx(30e-9)]]
    assert devtrace.top_ops(dw, 1) == [["edge_aggregate.10",
                                        pytest.approx(30e-9)]]
    assert devtrace.op_name("%fusion.12 = f32[2]{0} fusion(f32[2] %p)") \
        == "fusion.12"


def _ctx(dw, **kw):
    win = spans.Window(t0_s=0.0, t1_s=2.0, rounds=100, dispatch_s=1.5,
                       spans=[])
    base = dict(window=win, t_start=-5.0, device=dw, device_rounds=50,
                chips=1,
                peaks=peaks.lookup("TPU v5 lite"), silos=11, edges=22,
                params=1_000_000, samples=100 * 11 * 32,
                train_flops_per_sample=6e7)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_metric_readers_on_small_trace():
    dw = devtrace.reduce(SMALL_IR, 1000.0, 1100.0)
    ctx = _ctx(dw)
    read = cellmod.metric_reader
    assert read("round_ms")(ctx) == pytest.approx(20.0)
    assert read("setup_s")(ctx) == pytest.approx(5.0)
    assert read("host_ms.between_dispatch")(ctx) == pytest.approx(5.0)
    assert read("mfu.train")(ctx) == pytest.approx(
        6e7 * 35200 / 2.0 / 197e12 * 100)
    # one call moving (22 + 22) * 1e6 * 4 bytes in 30 ns
    assert read("edge_aggregate_roofline")(ctx) == pytest.approx(
        44 * 4e6 / 819e9 / 30e-9 * 100)
    assert read("collective_ms.halo")(ctx) == pytest.approx(
        5e-9 / 50 * 1e3)
    empty = devtrace.reduce({"annotation": [0, 1], "devices": {
        "/device:TPU:0": [["fusion", 1000.0, 10.0]]}}, 1000.0, 1100.0)
    assert read("edge_aggregate_roofline")(_ctx(empty)) is None
    assert read("collective_ms.halo")(_ctx(empty)) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.lookup("TPU v99")


def test_recorded_chip_trace():
    """A slice of a trace recorded on one TPU v5e (femnist/gaia
    multigraph), reduced to its IR: the ops line of the device holds
    the aggregation kernel, and the reduction's busy time never
    exceeds the window."""
    ir = json.loads((DATA / "chip_trace_ir.json").read_text())
    (dev,) = ir["devices"]
    evs = ir["devices"][dev]
    t0, t1 = evs[0][1], evs[-1][1] + evs[-1][2]
    dw = devtrace.reduce(ir, t0, t1)
    assert 0 < dw.busy_s <= dw.window_s
    assert any("edge_aggregate" in n for n in dw.op_seconds)


def test_load_cpu_profile(tmp_path):
    """`load` finds the annotation on the host plane of a real
    `.xplane.pb` (a CPU run has no device plane to read)."""
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=devtrace.profile_options())
    with jax.profiler.TraceAnnotation(devtrace.ANNOTATION):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ir = devtrace.load(devtrace.find_xspace(str(tmp_path)))
    assert ir["annotation"][1] > 0
    assert ir["devices"] == {}
    with pytest.raises(RuntimeError):
        devtrace.reduce(ir, 0.0, 1.0)


def test_window_from_run_fl_spans(tmp_path):
    """A tiny CPU `run_fl` writes the spans the window is read from:
    the window starts at the first eval's end, ends at the last's, and
    holds every later dispatch."""
    from repro.fl import run_fl
    c = cellmod.load("femnist_cnn.gaia.ring")
    c.traffic.update(batch_size=2, samples_per_silo=8, eval_every=2)
    kept = []
    with harness.recorders(kept):
        run_fl(harness.fl_config(c, 5, 6, str(tmp_path / "t.json")))
    (rec,) = kept
    names = [e["name"] for e in rec.host_events]
    assert names == (["compile+dispatch", "dispatch", "eval"]
                     + ["dispatch", "dispatch", "eval"] * 2)
    win = spans.window(rec.host_events, rec._epoch)
    assert win.rounds == 4
    evals = [e for e in rec.host_events if e["name"] == "eval"]
    assert win.t0_s == pytest.approx(
        rec._epoch + (evals[0]["t0_ms"] + evals[0]["dur_ms"]) / 1e3)
    assert 0 < win.dispatch_s < win.seconds
    assert spans.period_estimate(rec.host_events, 2) > 0
    first = spans.window(rec.host_events, rec._epoch, 0, 1)
    assert first.rounds == 2 and first.t0_s == win.t0_s
    assert first.t1_s < win.t1_s and first.dispatch_s < win.dispatch_s
    with pytest.raises(RuntimeError):
        spans.window(rec.host_events[:3], rec._epoch)


def test_traced_run_profiles_only_its_last_period(tmp_path):
    """With `--trace 1` the measured call runs one eval period more and
    the profiler starts at that period's first dispatch: the window the
    span metrics read ends before it, and the trace holds the
    annotation the device clock is aligned by."""
    c = cellmod.load("femnist_cnn.gaia.ring")
    c.traffic.update(batch_size=2, samples_per_silo=8, eval_every=3)
    m = harness.measure(c, 5, 1e-3, True, str(tmp_path))
    assert m.rounds == 3 * 3
    assert m.window.rounds == 3 and m.traced.rounds == 3
    assert m.window.t1_s == m.traced.t0_s < m.clock.perf0 < m.traced.t1_s
    assert m.ir["annotation"][1] > 0
    (tmp_path / "plain").mkdir()
    plain = harness.measure(c, 5, 1e-3, False, str(tmp_path / "plain"))
    assert plain.rounds == 2 * 3 and plain.traced is None
