"""The host-phase readers: `run_fl`'s `sample`, `copy` and `launch`
spans inside the untraced window, per round."""

import types

import pytest

from bench.core import cell as cellmod
from bench.core import harness, spans

PHASES = ("sample", "copy", "launch")


def _ctx(span_list):
    win = spans.Window(t0_s=1.0, t1_s=3.0, rounds=100, dispatch_s=1.0,
                       spans=span_list)
    return types.SimpleNamespace(window=win)


@pytest.mark.parametrize("phase", PHASES)
def test_reader_sums_spans_inside_the_window(phase):
    read = cellmod.metric_reader(f"host_ms.{phase}")
    inside = [(phase, 1.0, 1.25), (phase, 2.0, 2.05),
              ("dispatch", 1.3, 1.9), ("other", 1.0, 2.0)]
    outside = [(phase, 0.5, 0.9), (phase, 2.9, 3.1)]
    assert read(_ctx(inside + outside)) == pytest.approx(0.3 / 100 * 1e3)
    # a program that records no such span leaves the metric out
    assert read(_ctx([("dispatch", 1.3, 1.9)] + outside)) is None


def test_readers_on_a_run_fl_window(tmp_path):
    """A tiny CPU `run_fl`: every phase is read, the phases outside the
    dispatches (with the evals) fit in the host time between them, and
    the launches in the dispatches."""
    from repro.fl import run_fl
    c = cellmod.load("femnist_cnn.gaia.ring")
    c.traffic.update(batch_size=2, samples_per_silo=8, eval_every=2)
    kept = []
    with harness.recorders(kept):
        run_fl(harness.fl_config(c, 5, 6, str(tmp_path / "t.json")))
    (rec,) = kept
    win = spans.window(rec.host_events, rec._epoch)
    ctx = types.SimpleNamespace(window=win)
    got = {p: cellmod.metric_reader(f"host_ms.{p}")(ctx) for p in PHASES}
    assert all(v > 0 for v in got.values()), got
    between = cellmod.metric_reader("host_ms.between_dispatch")(ctx)
    evals = sum(b - a for n, a, b in win.spans if n == "eval"
                and a >= win.t0_s and b <= win.t1_s) / win.rounds * 1e3
    assert got["sample"] + got["copy"] + evals <= between
    assert got["launch"] <= win.dispatch_s / win.rounds * 1e3
