"""The numbers that decide `correct`, and how each is judged.

A training cell compares the first three dispatches of the measured
`run_fl` call (a dispatch is one call of the compiled cycle, R rounds)
with the plain reference (`reference.py`) that follows them from the
same seed:

    init_bits     elements of the starting rows that differ (exact)
    feed_bits     elements of the first three dispatches' batches that
                  differ from the reference's draw (exact)
    loss1_gap     |loss - ref| / ref of the first round (the forward at
                  the starting weights), the reference's forward run at
                  the matmul precision the configuration states
    loss_gap      the widest |loss - ref| / |ref| over their rounds
    change1_gap   the worst leaf's gap between the norms of the
    change3_gap   parameters' change after dispatch 1 (and 3), over the
                  reference's norm of that leaf or of the median leaf,
                  whichever is larger; a leaf is one parameter tensor
                  over every silo
    eval_answers  test answers on which the first eval's accuracy
                  differs from the reference's on the same rows

Leaves whose first gradient in the reference is under a thousandth of
the median leaf's move by round-off alone and are left out of the
change gaps. Each cell's `bench/limits/<workload>.json` says which
numbers it compares and why; the others are printed as readings.
"""

from __future__ import annotations

import math

import numpy as np


def kept_leaves(grad_leaf_norms: np.ndarray) -> np.ndarray:
    return grad_leaf_norms >= 1e-3 * np.median(grad_leaf_norms)


def loss_gap(losses, ref_losses) -> float:
    a, b = np.asarray(losses, np.float64), np.asarray(ref_losses, np.float64)
    if not np.all(np.isfinite(a)):
        return math.inf
    return float(np.max(np.abs(a - b) / np.abs(b)))


def change_gap(w0, rows, ref_rows, layout, keep) -> float:
    """Worst kept leaf's |‖Δ‖ - ‖Δ_ref‖| / max(‖Δ_ref‖, median ‖Δ_ref‖)."""
    got, ref = [], []
    for sl in layout.leaf_slices():
        got.append(float(np.linalg.norm(rows[:, sl] - w0[sl])))
        ref.append(float(np.linalg.norm(ref_rows[:, sl] - w0[sl])))
    got, ref = np.array(got), np.array(ref)
    if not np.all(np.isfinite(got)):
        return math.inf
    gaps = np.abs(got - ref) / np.maximum(ref, np.median(ref))
    return float(np.max(gaps[keep]))


def step_numbers(losses, rows: dict, ref, w0, layout, r: int,
                 loss1_ref: float) -> dict:
    """The gaps of one trajectory (the program's, the control's or a
    fault's) from the reference trajectory `ref`, over R-round steps;
    `loss1_ref` is the reference's first loss at the configured
    precision."""
    keep = kept_leaves(ref.grad_leaf_norms)
    return {
        "loss1_gap": loss_gap(losses[:1], [loss1_ref]),
        "loss_gap": loss_gap(losses[:3 * r], ref.losses[:3 * r]),
        **{f"change{k}_gap": change_gap(w0, rows[k * r], ref.snapshots[k * r],
                                        layout, keep) for k in (1, 3)},
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(all within limits, {name: {"value", "limit"}}) over the numbers
    that have a limit; a number that is not finite fails."""
    compared = {k: {"value": values[k], "limit": limits[k]["limit"]}
                for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in compared.values())
    return ok, compared
