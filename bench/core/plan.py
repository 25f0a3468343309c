"""The round plan the program fed its cycle, held to its construction.

The reference follows the plan of the run: which directed edges are
strong in each round, and the mixing weights. So that a wrong plan
cannot pass because the reference follows it, the plan is checked here
against what its construction guarantees, worked out from the edges
alone and from the cell's stated plan (`bench/limits/<workload>.json`,
key `plan`):

    plan_overlay   breaks of "the overlay is one cycle through all N
                   silos, each link in both directions": the ring that
                   the multigraph's overlay and the ring topology both
                   are (self loops, doubled or one-way edges, silos of
                   degree other than 2, components past the first)
    plan_weights   mixing weights further than 1e-6 from the
                   Metropolis-Hastings weights of that overlay: an edge
                   j -> i gets 1 / (1 + max(deg i, deg j)), the silo
                   keeps the rest
    plan_strong    strong masks that break the multigraph's parse: a
                   link strong one way and not the other, a link that
                   is not strong in some `t` consecutive rounds of the
                   repeating cycle (no link's multiplicity exceeds t),
                   a dispatch whose rounds or strong edge-rounds differ
                   from the stated plan

Each is a count of breaks, compared exactly. What this cannot see: that
the overlay is the Christofides tour of the network's delays and that
each link's multiplicity is Algorithm 1's; the stated counts pin the
cycle's length and its strong share.
"""

from __future__ import annotations

import numpy as np

TOL = 1e-6


def _components(n: int, src, dst) -> int:
    parent = list(range(n))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    for a, b in zip(src.tolist(), dst.tolist()):
        parent[find(a)] = find(b)
    return len({find(i) for i in range(n)})


def overlay_breaks(n: int, src, dst) -> int:
    pairs = list(zip(src.tolist(), dst.tolist()))
    have = set(pairs)
    out_deg = np.bincount(src, minlength=n)
    in_deg = np.bincount(dst, minlength=n)
    return (int(np.sum(src == dst)) + len(pairs) - len(have)
            + sum((b, a) not in have for a, b in pairs)
            + int(np.sum(np.abs(out_deg - 2)) + np.sum(np.abs(in_deg - 2)))
            + _components(n, src, dst) - 1)


def mh_weights(n: int, src, dst):
    """(per-edge, per-silo) Metropolis-Hastings weights of the edges."""
    deg = np.bincount(dst, minlength=n)
    coeff = 1.0 / (1.0 + np.maximum(deg[src], deg[dst]))
    diag = 1.0 - np.bincount(dst, weights=coeff, minlength=n)
    return coeff, diag


def strong_breaks(src, dst, strong, t: int, rounds: int,
                  strong_edge_rounds: int) -> int:
    """Breaks in one dispatch's (R, 2E) strong mask (one whole cycle)."""
    index = {p: e for e, p in enumerate(zip(src.tolist(), dst.tolist()))}
    rev = np.array([index.get((b, a), e)
                    for e, (a, b) in enumerate(zip(src.tolist(),
                                                   dst.tolist()))])
    r = strong.shape[0]
    cyc = np.concatenate([strong] * (1 + -(-t // r)))
    uncovered = sum(int(np.sum(~cyc[k:k + t].any(axis=0)))
                    for k in range(r))
    return (int(np.sum(strong != strong[:, rev])) + uncovered
            + abs(r - rounds) + abs(int(strong.sum()) - strong_edge_rounds))


def numbers(n: int, src, dst, dispatches: list, t: int,
            stated: dict) -> dict:
    """The three counts over the captured dispatches, each a list
    (strong, coeffs, diag) of (R, 2E), (R, 2E), (R, N)."""
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    coeff, diag = mh_weights(n, src, dst)
    weights = sum(int(np.sum(np.abs(c - coeff) > TOL)
                      + np.sum(np.abs(d - diag) > TOL))
                  for _, c, d in dispatches)
    strong = sum(strong_breaks(src, dst, np.asarray(s, bool), t,
                               stated["rounds_per_dispatch"],
                               stated["strong_edge_rounds"])
                 for s, _, _ in dispatches)
    return {"plan_overlay": overlay_breaks(n, src, dst),
            "plan_weights": weights, "plan_strong": strong}
