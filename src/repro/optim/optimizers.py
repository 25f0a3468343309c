"""SGD(+momentum) and AdamW over pytrees, plus schedules and clipping."""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable  # (params, grads, state, lr_scale=1.0) -> (params, state)


def _tmap(f, *trees):
    return jax.tree.map(f, *trees)


def sgd(lr: float, momentum: float = 0.0,
        weight_decay: float = 0.0) -> Optimizer:
    from repro.fl.flat import pin_dtype  # lazy: optim must not import fl

    def init(params):
        if momentum == 0.0:
            return {"step": jnp.zeros((), jnp.int32)}
        return {"step": jnp.zeros((), jnp.int32),
                "mu": _tmap(jnp.zeros_like, params)}

    def update(params, grads, state, lr_scale=1.0):
        step = state["step"] + 1
        lr_t = lr * lr_scale
        if weight_decay:
            grads = _tmap(lambda g, p: g + weight_decay * p.astype(g.dtype),
                          grads, params)
        # `pin_dtype` makes both paths bit-identical between this
        # per-leaf layout and the flat (N, T) layout (see fl/flat.py).
        # It pins every mul-feeding-add site to rounded values, which
        # LLVM would otherwise FMA-contract differently per layout. It
        # also pins the gradient operand of the momentum add: when a
        # leaf's gradient ends in a scatter-add (an embedding lookup's
        # transpose), XLA rewrites `x + scatter(0, u)` into
        # `scatter(x, u)` in one layout and not the other, which
        # reorders the float additions.
        if momentum == 0.0:
            new = _tmap(
                lambda p, g: p - pin_dtype(lr_t * g, step).astype(p.dtype),
                params, grads)
            return new, {"step": step}
        mu = _tmap(lambda m, g: pin_dtype(momentum * m, step) +
                   pin_dtype(g, step),
                   state["mu"], grads)
        new = _tmap(lambda p, m: p - pin_dtype(lr_t * m, step).astype(p.dtype),
                    params, mu)
        return new, {"step": step, "mu": mu}

    return Optimizer(init, update)


def flat_sgd(lr: float, momentum: float = 0.0,
             weight_decay: float = 0.0) -> Optimizer:
    """SGD(+momentum) over a flat silo-parameter buffer.

    Params and grads are single `(N, T)` arrays (the flat FL runtime's
    packed layout, repro/fl/flat.py) — the update is one elementwise op
    over one contiguous buffer instead of a pytree traversal, and is
    numerically identical to `vmap(sgd().update)` over the silo axis.
    The step counter is a shared scalar (identical across silos by
    construction in DPASGD's synchronized rounds).
    """

    from repro.fl.flat import pin_dtype  # lazy: optim must not import fl

    def init(w):
        state = {"step": jnp.zeros((), jnp.int32)}
        if momentum != 0.0:
            state["mu"] = jnp.zeros_like(w)
        return state

    def update(w, g, state, lr_scale=1.0):
        step = state["step"] + 1
        lr_t = lr * lr_scale
        if weight_decay:
            g = g + weight_decay * w.astype(g.dtype)
        # same pinned sites as `sgd` — the two paths are bit-for-bit
        # equal in every layout (tests/test_flat_runtime.py holds them
        # exactly equal, not allclose).
        if momentum == 0.0:
            return (w - pin_dtype(lr_t * g, step).astype(w.dtype),
                    {"step": step})
        mu = pin_dtype(momentum * state["mu"], step) + pin_dtype(g, step)
        return (w - pin_dtype(lr_t * mu, step).astype(w.dtype),
                {"step": step, "mu": mu})

    return Optimizer(init, update)


def adamw(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": _tmap(lambda p: jnp.zeros_like(p, jnp.float32), params),
            "v": _tmap(lambda p: jnp.zeros_like(p, jnp.float32), params),
        }

    def update(params, grads, state, lr_scale=1.0):
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        m = _tmap(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                  state["m"], grads)
        v = _tmap(lambda v_, g: b2 * v_ + (1 - b2) *
                  jnp.square(g.astype(jnp.float32)), state["v"], grads)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t
        lr_t = lr * lr_scale

        def upd(p, m_, v_):
            mhat = m_ / bc1
            vhat = v_ / bc2
            delta = mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                delta = delta + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * delta).astype(p.dtype)

        return _tmap(upd, params, m, v), {"step": step, "m": m, "v": v}

    return Optimizer(init, update)


def cosine_schedule(base_lr: float, total_steps: int,
                    warmup: int = 0) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = jnp.minimum(step / jnp.maximum(warmup, 1), 1.0)
        prog = jnp.clip((step - warmup) / max(total_steps - warmup, 1), 0, 1)
        return base_lr * warm * 0.5 * (1 + jnp.cos(jnp.pi * prog))

    return lr


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), norm
