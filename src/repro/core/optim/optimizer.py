"""First-order optimizers (paper §4.2): defined over Variable/Tensor ops so
they are open to experimentation (distributed updates, in-place tricks).

Two call styles, one implementation:

* imperative (paper Listing 9): ``opt.step(); opt.zeroGrad()`` over a
  module's Variables;
* functional (production loop): ``new_params, new_state = opt.apply(params,
  grads, state)`` over pytrees — this is the form the pjit'd trainer uses,
  and state entries carry sharding rules so optimizer state can be
  ZeRO-sharded across the mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from ..autograd import Variable


class Optimizer:
    """Base: functional ``init``/``apply`` + imperative Variable bridge."""

    def __init__(self, params: Sequence[Variable] | None = None,
                 state_dtype=None):
        self._vars = list(params) if params is not None else None
        self._state = None
        self.state_dtype = state_dtype
        self.step_count = 0

    # -- functional API -------------------------------------------------------
    def init(self, params: Any) -> Any:
        return jax.tree.map(self._init_leaf, params)

    def apply(self, params: Any, grads: Any, state: Any,
              lr: float | jax.Array) -> tuple[Any, Any]:
        self.step_count += 1
        count = self.step_count
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state)
        new_p, new_s = [], []
        for p, g, s in zip(flat_p, flat_g, flat_s):
            np_, ns_ = self._update_leaf(p, g, s, lr, count)
            new_p.append(np_)
            new_s.append(ns_)
        return (jax.tree.unflatten(treedef, new_p),
                jax.tree.unflatten(treedef, new_s))

    def apply_with_count(self, params, grads, state, lr, count):
        """Pure form for jit: caller carries the step count."""
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state)
        out = [self._update_leaf(p, g, s, lr, count)
               for p, g, s in zip(flat_p, flat_g, flat_s)]
        return (jax.tree.unflatten(treedef, [o[0] for o in out]),
                jax.tree.unflatten(treedef, [o[1] for o in out]))

    def _init_leaf(self, p) -> Any:
        raise NotImplementedError

    def _update_leaf(self, p, g, s, lr, count) -> tuple[Any, Any]:
        raise NotImplementedError

    # -- imperative API (paper Listing 9) ----------------------------------------
    def step(self, lr: float | None = None) -> None:
        if self._vars is None:
            raise RuntimeError("imperative step() needs params at __init__")
        if self._state is None:
            self._state = [self._init_leaf(v.data) for v in self._vars]
        self.step_count += 1
        use_lr = lr if lr is not None else getattr(self, "lr", None)
        for i, v in enumerate(self._vars):
            if v.grad is None:
                continue
            v.data, self._state[i] = self._update_leaf(
                v.data, v.grad, self._state[i], use_lr, self.step_count)

    def zeroGrad(self) -> None:  # noqa: N802 - paper-faithful name
        if self._vars is not None:
            for v in self._vars:
                v.zero_grad()

    zero_grad = zeroGrad

    def state_sharding_like(self, param_sharding: Any) -> Any:
        """Map a param's sharding rule onto this optimizer's state for it.

        Moment-style states are shaped like the param, so they inherit the
        param's logical axes — this is what lets the trainer ZeRO-shard
        optimizer state across the data axis.
        """
        return jax.tree.map(lambda _: param_sharding,
                            self._init_leaf(jnp.zeros(())))

    def _cast(self, x):
        return x if self.state_dtype is None else x.astype(self.state_dtype)


class SGD(Optimizer):
    def __init__(self, params=None, lr: float = 0.01, momentum: float = 0.0,
                 weight_decay: float = 0.0, nesterov: bool = False, **kw):
        super().__init__(params, **kw)
        self.lr, self.momentum = lr, momentum
        self.weight_decay, self.nesterov = weight_decay, nesterov

    def _init_leaf(self, p):
        if self.momentum == 0.0:
            return ()
        return self._cast(jnp.zeros_like(p))

    def _update_leaf(self, p, g, s, lr, count):
        lr = self.lr if lr is None else lr
        if self.weight_decay:
            g = g + self.weight_decay * p
        if self.momentum == 0.0:
            return p - lr * g.astype(p.dtype), ()
        buf = self.momentum * s + g.astype(s.dtype)
        d = (g + self.momentum * buf.astype(g.dtype)) if self.nesterov \
            else buf.astype(g.dtype)
        return p - lr * d.astype(p.dtype), buf


SGDOptimizer = SGD  # paper-faithful alias (Listing 9)


class Adam(Optimizer):
    def __init__(self, params=None, lr: float = 1e-3, b1: float = 0.9,
                 b2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 0.0, **kw):
        super().__init__(params, **kw)
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps
        self.weight_decay = weight_decay

    def _init_leaf(self, p):
        # moments start in the float32 that _update_leaf returns them in,
        # so a jitted step sees one state dtype from its first call on
        return {"m": self._cast(jnp.zeros_like(p, jnp.float32)),
                "v": self._cast(jnp.zeros_like(p, jnp.float32))}

    def _update_leaf(self, p, g, s, lr, count):
        lr = self.lr if lr is None else lr
        g32 = g.astype(jnp.float32)
        m = self.b1 * s["m"].astype(jnp.float32) + (1 - self.b1) * g32
        v = self.b2 * s["v"].astype(jnp.float32) + (1 - self.b2) * g32 * g32
        mhat = m / (1 - self.b1 ** count)
        vhat = v / (1 - self.b2 ** count)
        update = mhat / (jnp.sqrt(vhat) + self.eps)
        if self.weight_decay:
            update = update + self.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * update).astype(p.dtype)
        return new_p, {"m": self._cast(m), "v": self._cast(v)}


class AdamW(Adam):
    """Decoupled weight decay (the production-default optimizer)."""

    def __init__(self, params=None, lr: float = 1e-3, b1: float = 0.9,
                 b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, **kw):
        super().__init__(params, lr=lr, b1=b1, b2=b2, eps=eps,
                         weight_decay=0.0, **kw)
        self.decoupled_wd = weight_decay

    def _update_leaf(self, p, g, s, lr, count):
        lr_v = self.lr if lr is None else lr
        new_p, new_s = super()._update_leaf(p, g, s, lr, count)
        if self.decoupled_wd:
            new_p = new_p - (lr_v * self.decoupled_wd * p.astype(
                jnp.float32)).astype(p.dtype)
        return new_p, new_s


class Adafactor(Optimizer):
    """Factored second moment — the memory-frugal option for huge models."""

    def __init__(self, params=None, lr: float = 1e-2, decay: float = 0.8,
                 eps: float = 1e-30, **kw):
        super().__init__(params, **kw)
        self.lr, self.decay, self.eps = lr, decay, eps

    def _init_leaf(self, p):
        if p.ndim >= 2:
            return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
        return {"v": jnp.zeros_like(p, dtype=jnp.float32)}

    def _update_leaf(self, p, g, s, lr, count):
        lr = self.lr if lr is None else lr
        g32 = g.astype(jnp.float32)
        beta = 1.0 - count ** (-self.decay)
        g2 = g32 * g32 + self.eps
        if p.ndim >= 2:
            vr = beta * s["vr"] + (1 - beta) * g2.mean(axis=-1)
            vc = beta * s["vc"] + (1 - beta) * g2.mean(axis=-2)
            rfac = (vr / vr.mean(axis=-1, keepdims=True))[..., None]
            update = g32 / (jnp.sqrt(rfac * vc[..., None, :]) + 1e-12)
            new_s = {"vr": vr, "vc": vc}
        else:
            v = beta * s["v"] + (1 - beta) * g2
            update = g32 / (jnp.sqrt(v) + 1e-12)
            new_s = {"v": v}
        # update clipping (rms <= 1)
        rms = jnp.sqrt(jnp.mean(update * update) + 1e-12)
        update = update / jnp.maximum(1.0, rms)
        return (p.astype(jnp.float32) - lr * update).astype(p.dtype), new_s


# -- gradient utilities ---------------------------------------------------------

def clip_by_global_norm(grads: Any, max_norm: float) -> tuple[Any, jax.Array]:
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(
        g.dtype), grads), gnorm


# -- LR schedules ---------------------------------------------------------------

def cosine_schedule(base_lr: float, warmup: int, total: int,
                    min_ratio: float = 0.1) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / jnp.maximum(1.0, warmup)
        frac = (step - warmup) / jnp.maximum(1.0, total - warmup)
        frac = jnp.clip(frac, 0.0, 1.0)
        cos = base_lr * (min_ratio + (1 - min_ratio)
                         * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        return jnp.where(step < warmup, warm, cos)

    return lr


def linear_schedule(base_lr: float, warmup: int, total: int) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / jnp.maximum(1.0, warmup)
        lin = base_lr * jnp.clip((total - step) / jnp.maximum(
            1.0, total - warmup), 0.0, 1.0)
        return jnp.where(step < warmup, warm, lin)

    return lr
