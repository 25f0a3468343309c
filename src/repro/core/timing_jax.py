"""Device-side Eq. 4/5 grid engine (DESIGN.md §15).

The numpy `timing.TimingGrid` steps all recurrence cells together but
still runs one Python-level round step per live round, plus a per-cell
Python hashing loop for orbit detection. That is fine for the 105
paper sweep cells (short transients, orbits lock within a few hundred
rounds) but is the binding constraint on *population search*, where
thousands of random candidate multigraphs — whose transients are long
and whose orbits rarely lock early — must be scored per generation.

This module lifts the whole recurrence onto the accelerator as one
`lax.scan` over rounds with the stacked ``(C, S_max, E_max)`` cell
axis:

* the Eq. 4 branch select becomes `lax.select_n` over the transition
  code (``code = 2*prev + cur`` — exactly the numpy grid's encoding),
  so the four branches are computed vectorized and gathered in one op
  (profiled: the select tree is a negligible fraction of the scan step
  next to the per-round ``strong``/``trans`` row gathers, so no Pallas
  kernel is warranted);
* the per-cell phase ``k % S_c`` indexes each cell's own state row, so
  heterogeneous state counts batch without host-side grouping;
* everything runs in f64 under `jax.enable_x64(True)` — scoped
  to this module's calls so the f32 FL runtime in the same process is
  untouched — and every operation is an elementwise IEEE-754 op or an
  order-exact max reduction, which makes the output BIT-FOR-BIT equal
  to the numpy grid wherever f64 is native (asserted on all 105 paper
  cells by ``python -m repro.core.sweep --check`` and
  tests/test_population.py). A TPU emulates f64, and its sums round
  differently by a few ulp: there the check holds the reports within
  `sweep.EMULATED_F64_RTOL` instead.

Orbit detection stays on the host, by design: the numpy grid's
splitmix snapshot hash is an *exact verifier* (a hit is confirmed by
comparing full ``(phase, d_k, d_{k-1}, tau_k)`` snapshots bit-for-bit
before any extrapolation fires), and that verification is inherently
data-dependent control flow — the one thing a fixed-length `lax.scan`
cannot express without per-round host sync, which would cost more than
it saves. The device engine therefore always steps the full horizon;
the host engine remains the oracle AND the better choice for few
long-horizon cells with short transients, while the device engine wins
on many-candidate population scoring (the `design/grid_jax` bench row
records the crossover).

Shape discipline: `jax.jit` specializes on ``(C, S_max, E_max,
num_rounds)``. `grid_recurrence_taus` buckets C and S_max up to powers
of two with inert padded rows/states (d0 = 0, code = T_SS, strong =
False, lone = -inf — the same inert-padding contract as
`timing.build_timing_grid`), so a population whose candidate count or
state count drifts between generations reuses one compiled program
instead of recompiling per generation.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.timing import T_SS

__all__ = ["grid_recurrence_taus"]


def _bucket(n: int) -> int:
    """Next power of two >= n (>= 1) — the compile-cache bucket."""
    return 1 << max(int(n) - 1, 0).bit_length()


@partial(jax.jit, static_argnames=("num_rounds",))
def _grid_taus(d0, pair_comp, strong, trans, lone_comp, num_states,
               num_rounds):
    """(C, num_rounds) f64 taus — the jitted scan over rounds.

    ``d0`` / ``pair_comp`` may be ``(E,)`` (shared by every cell — the
    population scorer's case, uploaded once and reused across
    generations) or ``(C, E)`` (per-cell — the sweep grid's case); both
    broadcast to the stacked shape inside the trace.
    """
    C, _, E = strong.shape
    rows = jnp.arange(C)
    d0b = jnp.broadcast_to(d0, (C, E))
    pcb = jnp.broadcast_to(pair_comp, (C, E))

    def step(carry, k):
        d_cur, d_prev, prev_tau = carry
        s = k % num_states                       # (C,) per-cell phase
        st = strong[rows, s]                     # (C, E) row gather
        code = trans[rows, s]                    # (C, E)
        # The four Eq. 4 branches, computed vectorized and gathered by
        # transition code (T_WW=0, T_WS=1, T_SW=2, T_SS=3):
        ww = prev_tau[:, None] + d_cur
        sw = jnp.broadcast_to(prev_tau[:, None], d_cur.shape)
        ws = jnp.maximum(pcb, d_cur - d_prev)
        d_next = lax.select_n(code.astype(jnp.int32), ww, ws, sw, d_cur)
        # Round 0 applies no transition (matches the host engines).
        first = k == 0
        d_next = jnp.where(first, d_cur, d_next)
        d_p = jnp.where(first, d_prev, d_cur)
        tau = jnp.max(jnp.where(st, d_next, -jnp.inf), axis=1)  # Eq. 5
        tau = jnp.maximum(tau, lone_comp[rows, s])
        return (d_next, d_p, tau), tau

    (_, _, _), taus = lax.scan(step, (d0b, d0b, jnp.zeros(C)),
                               jnp.arange(num_rounds))
    return taus.T


def grid_recurrence_taus(d0, pair_comp, strong, trans, lone_comp,
                         num_states, num_rounds: int, *,
                         bucket: bool = True) -> np.ndarray:
    """Device twin of `timing._grid_recurrence_taus`: ``(C, R)`` f64.

    Accepts the same stacked arrays as the numpy grid engine —
    ``strong``/``trans`` ``(C, S_max, E_max)``, ``lone_comp``
    ``(C, S_max)``, ``num_states`` ``(C,)`` — with ``d0``/``pair_comp``
    either per-cell ``(C, E_max)`` or shared ``(E_max,)``. Inputs may
    be numpy arrays or already-resident jax arrays (the population
    scorer keeps its shared buffers on device across generations).

    ``bucket=True`` pads C and S_max up to powers of two with inert
    rows/states so nearby shapes share one compiled program; padding
    cannot perturb live rows (phantom cells never mix with real ones —
    the cell axis is data-parallel) and padded output rows are sliced
    off before returning.
    """
    if np.ndim(strong) != 3:
        raise ValueError(
            f"strong must be (C, S, E), got {np.shape(strong)}")
    c, s, _ = np.shape(strong)
    # Every jnp conversion happens INSIDE the x64 scope: outside it,
    # jnp.asarray would silently downcast f64 -> f32 / i64 -> i32 and
    # break bit-exactness with the numpy oracle.
    with jax.enable_x64(True):
        strong = jnp.asarray(strong)
        trans = jnp.asarray(trans)
        lone_comp = jnp.asarray(lone_comp, jnp.float64)
        num_states = jnp.asarray(num_states, jnp.int64)
        d0 = jnp.asarray(d0, jnp.float64)
        pair_comp = jnp.asarray(pair_comp, jnp.float64)
        if bucket:
            cp, sp = _bucket(c) - c, _bucket(s) - s
            if cp or sp:
                strong = jnp.pad(strong, ((0, cp), (0, sp), (0, 0)))
                trans = jnp.pad(trans, ((0, cp), (0, sp), (0, 0)),
                                constant_values=T_SS)
                lone_comp = jnp.pad(lone_comp, ((0, cp), (0, sp)),
                                    constant_values=-jnp.inf)
                num_states = jnp.pad(num_states, (0, cp),
                                     constant_values=1)
                if d0.ndim == 2:
                    d0 = jnp.pad(d0, ((0, cp), (0, 0)))
                    pair_comp = jnp.pad(pair_comp, ((0, cp), (0, 0)))
        taus = _grid_taus(d0, pair_comp, strong, trans, lone_comp,
                          num_states, int(num_rounds))
        out = np.asarray(taus)
    return out[:c]
