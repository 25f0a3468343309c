"""The measured window, read from `run_fl`'s own host spans.

With `FLConfig.trace` set, `run_fl` records wall-clock spans
(`time.perf_counter`, in ms from its recorder's epoch):

    compile+dispatch   the first dispatch of the cycle (trace, compile
                       or cache load, run, loss sync)
    dispatch           every later dispatch, ending on the loss sync
    eval               each evaluation, every `eval_every` rounds

A window runs from the end of one `eval` to the end of a later one
(by default the first and the last), so it holds whole eval periods:
every dispatch, every eval and every host gap between them. No span,
no window: the harness never falls back to timing the whole call.
"""

from __future__ import annotations

import dataclasses

SPAN_FIRST = "compile+dispatch"
SPAN_DISPATCH = "dispatch"
SPAN_EVAL = "eval"


@dataclasses.dataclass
class Window:
    t0_s: float            # perf_counter seconds
    t1_s: float
    rounds: int            # rounds completed inside
    dispatch_s: float      # summed `dispatch` spans inside
    spans: list            # (name, start_s, end_s), every host span

    @property
    def seconds(self) -> float:
        return self.t1_s - self.t0_s


def window(host_events: list, epoch_s: float, first: int = 0,
           last: int = -1) -> Window:
    """The window of one `run_fl` call from its recorder's host spans,
    from the end of its `first` eval to the end of its `last`."""
    spans = [(e["name"], epoch_s + e["t0_ms"] / 1e3,
              epoch_s + (e["t0_ms"] + e["dur_ms"]) / 1e3, e["args"])
             for e in host_events]
    evals = [s for s in spans if s[0] == SPAN_EVAL]
    if not any(s[0] == SPAN_FIRST for s in spans) or len(evals) < 2:
        raise RuntimeError(
            f"run_fl wrote {len(evals)} {SPAN_EVAL!r} spans and "
            f"{sum(s[0] == SPAN_FIRST for s in spans)} {SPAN_FIRST!r}: "
            "a window needs the first dispatch and two evals")
    a, b = evals[first], evals[last]
    t0, t1 = a[2], b[2]
    inside = [s for s in spans if s[0] == SPAN_DISPATCH
              and s[1] >= t0 and s[2] <= t1]
    return Window(t0_s=t0, t1_s=t1,
                  rounds=b[3]["round"] - a[3]["round"],
                  dispatch_s=sum(b - a for _, a, b, _ in inside),
                  spans=[s[:3] for s in spans])


def period_estimate(host_events: list, eval_every: int) -> float:
    """Seconds of the rounds of one eval period, from a warm-up call's
    spans: the steady pitch of its dispatches after the first (loss
    sync to loss sync, host work included) times `eval_every`. Its one
    eval, at its end, compiles, so it is left out; a window of whole
    periods then runs a little over the seconds asked for, by its
    evals."""
    disp = [e for e in host_events if e["name"] in (SPAN_FIRST,
                                                    SPAN_DISPATCH)]
    ends = [e["t0_ms"] + e["dur_ms"] for e in disp]
    if len(ends) < 2:
        raise RuntimeError("the warm-up wrote too few spans to time")
    rounds = [e["args"]["rounds"] for e in disp[1:]]
    return (ends[-1] - ends[0]) / sum(rounds) * eval_every / 1e3
