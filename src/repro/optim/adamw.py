"""AdamW with global-norm clipping and mixed-precision master params.

Model params may live in bf16; the optimizer keeps an fp32 master copy plus
fp32 moments.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1


class OptState(NamedTuple):
    step: jax.Array
    master: Any       # fp32 copy of params
    m: Any
    v: Any


def init_opt_state(params, ocfg: AdamWConfig) -> OptState:
    f32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    zeros = jax.tree.map(jnp.zeros_like, f32)
    return OptState(step=jnp.zeros((), jnp.int32), master=f32,
                    m=zeros, v=jax.tree.map(jnp.zeros_like, f32))


def lr_schedule(step, ocfg: AdamWConfig):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(ocfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - ocfg.warmup_steps) /
                    max(ocfg.total_steps - ocfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return ocfg.lr * warm * (ocfg.min_lr_frac + (1 - ocfg.min_lr_frac) * cos)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


_NO_DECAY_SUFFIXES = ("scale", "bias")


def _decay_mask(path) -> bool:
    name = str(getattr(path[-1], "key", path[-1]))
    return not any(name.endswith(s) for s in _NO_DECAY_SUFFIXES)


def adamw_update(grads, opt: OptState, params, ocfg: AdamWConfig):
    """Returns (new_params, new_opt, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, ocfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    step = opt.step + 1
    lr = lr_schedule(step, ocfg)
    b1, b2 = ocfg.b1, ocfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(path, g, m, v, mp):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * jnp.square(g)
        upd_ = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + ocfg.eps)
        if _decay_mask(path):
            upd_ = upd_ + ocfg.weight_decay * mp
        mp_new = mp - lr * upd_
        return m_new, v_new, mp_new

    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    treedef = jax.tree.structure(grads)
    ms = jax.tree.leaves(opt.m)
    vs = jax.tree.leaves(opt.v)
    mps = jax.tree.leaves(opt.master)
    out_m, out_v, out_p = [], [], []
    for (path, g), m, v, mp in zip(flat, ms, vs, mps):
        m_new, v_new, mp_new = upd(path, g, m, v, mp)
        out_m.append(m_new)
        out_v.append(v_new)
        out_p.append(mp_new)
    new_master = jax.tree.unflatten(treedef, out_p)
    new_opt = OptState(step=step, master=new_master,
                       m=jax.tree.unflatten(treedef, out_m),
                       v=jax.tree.unflatten(treedef, out_v))
    new_params = jax.tree.map(lambda mp, p: mp.astype(p.dtype), new_master,
                              params)
    return new_params, new_opt, {"grad_norm": gnorm, "lr": lr}
