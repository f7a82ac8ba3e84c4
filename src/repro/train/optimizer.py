"""Optimizers in pure JAX: AdamW with optional int8-quantized moments.

The int8 state (block-wise absmax scaling, like 8-bit Adam) is a
distributed-optimization feature: it cuts optimizer-state HBM from 8 to 2
bytes/param, which is what lets the 671B/1T MoE configs fit a single
16GB-HBM v5e pod (see EXPERIMENTS.md §Dry-run memory table).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.common import pytree

PyTree = Any

_QBLOCK = 256  # elements per quantization block


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float | Callable[[jax.Array], jax.Array] = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip_norm: Optional[float] = 1.0
    state_dtype: str = "float32"  # "float32" | "bfloat16" | "int8"


# ---------------------------------------------------------------------------
# int8 block-quantized tensors
# ---------------------------------------------------------------------------

def _quantize_i8(x: jax.Array) -> dict:
    """Per-row (last-dim) absmax int8 quantization.

    STRUCTURE-PRESERVING on purpose: ``q`` keeps the parameter's exact shape
    (int8) and ``scale`` is (..., 1), so both inherit the parameter's
    PartitionSpec unchanged and the dequantize fuses elementwise into the
    update — a flat block layout forces resharding/replication of f32
    moment temporaries (observed: +30 GiB/device on the 7B dense cells)."""
    if x.ndim == 0:
        scale = jnp.maximum(jnp.abs(x), 1e-12) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32)}
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def _dequantize_i8(qt: dict, shape, dtype=jnp.float32) -> jax.Array:
    x = qt["q"].astype(jnp.float32) * qt["scale"]
    return x.reshape(shape).astype(dtype)


def _make_moment(x: jax.Array, state_dtype: str):
    if state_dtype == "int8":
        return _quantize_i8(jnp.zeros_like(x, dtype=jnp.float32))
    return jnp.zeros(x.shape, jnp.dtype(state_dtype))


def _read_moment(m, shape, state_dtype: str) -> jax.Array:
    if state_dtype == "int8":
        return _dequantize_i8(m, shape)
    return m.astype(jnp.float32)


def _write_moment(val: jax.Array, state_dtype: str):
    if state_dtype == "int8":
        return _quantize_i8(val)
    return val.astype(jnp.dtype(state_dtype))


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def adamw_init(params: PyTree, cfg: AdamWConfig) -> dict:
    # "int8" quantizes the FIRST moment only; the second moment uses bf16 —
    # linear int8 zeros out small v entries and 1/sqrt(v) then explodes
    # (classic 8-bit-Adam failure; bnb solves it with nonlinear quantiles,
    # we solve it with bf16's wide exponent). 3 bytes/param total.
    mk = partial(_make_moment, state_dtype=cfg.state_dtype)
    vk = partial(_make_moment,
                 state_dtype="bfloat16" if cfg.state_dtype == "int8"
                 else cfg.state_dtype)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(mk, params),
        "v": jax.tree.map(vk, params),
    }


@partial(jax.jit, static_argnames=("cfg",))
def _leaf_update(g, m, v, p, clip, lr, c1, c2, *, cfg: AdamWConfig):
    """One parameter leaf's AdamW step — elementwise only, one small
    program per leaf shape instead of an eager op each."""
    v_dtype = "bfloat16" if cfg.state_dtype == "int8" else cfg.state_dtype
    g32 = g.astype(jnp.float32) * clip
    m32 = _read_moment(m, g.shape, cfg.state_dtype)
    v32 = _read_moment(v, g.shape, v_dtype)
    m32 = cfg.b1 * m32 + (1.0 - cfg.b1) * g32
    v32 = cfg.b2 * v32 + (1.0 - cfg.b2) * jnp.square(g32)
    mh = m32 / c1
    vh = v32 / c2
    delta = mh / (jnp.sqrt(vh) + cfg.eps)
    if cfg.weight_decay:
        delta = delta + cfg.weight_decay * p.astype(jnp.float32)
    newp = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
    return newp, _write_moment(m32, cfg.state_dtype), _write_moment(v32, v_dtype)


def adamw_update(grads: PyTree, state: dict, params: PyTree, cfg: AdamWConfig):
    """Returns (new_params, new_state). Grad clip + decoupled weight decay.

    The global-norm clip factor is computed outside the per-leaf programs
    and passed in: a program that couples every gradient through the norm
    overflowed the stack of XLA's TPU compiler on the chip."""
    step = state["step"] + 1
    clip = jnp.float32(1.0)
    if cfg.grad_clip_norm is not None:
        gnorm = pytree.global_norm(grads)
        clip = jnp.minimum(1.0, cfg.grad_clip_norm / (gnorm + 1e-9))

    lr = cfg.lr(step) if callable(cfg.lr) else cfg.lr
    c1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    c2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    outs = [_leaf_update(g, m, v, p, clip, lr, c1, c2, cfg=cfg)
            for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in outs])
    new_m = treedef.unflatten([o[1] for o in outs])
    new_v = treedef.unflatten([o[2] for o in outs])
    return new_p, {"step": step, "m": new_m, "v": new_v}


def adamw_step(loss_fn: Callable, cfg: AdamWConfig) -> Callable:
    """One training step of ``loss_fn(params, *batch)``:
    ``step(params, state, *batch) -> (params, state, loss)`` — a jitted
    gradient, then ``adamw_update``. The two are not fused into one
    program: the global-norm clip would couple every weight gradient's dot
    to every update, and XLA's TPU compiler overflows its stack estimating
    that fusion."""
    grad = jax.jit(jax.value_and_grad(loss_fn))

    def step(params, state, *batch):
        loss, grads = grad(params, *batch)
        params, state = adamw_update(grads, state, params, cfg)
        return params, state, loss

    return step


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------


def cosine_schedule(peak_lr: float, warmup: int, total: int, floor: float = 0.1):
    def sched(step):
        step = step.astype(jnp.float32)
        warm = peak_lr * step / max(1, warmup)
        t = jnp.clip((step - warmup) / max(1, total - warmup), 0.0, 1.0)
        cos = peak_lr * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
        return jnp.where(step < warmup, warm, cos)

    return sched


def constant_schedule(lr: float):
    return lambda step: jnp.full((), lr, jnp.float32)


# ---------------------------------------------------------------------------
# Adafactor (factored second moment) — the memory saver for the MoE giants:
# optimizer state is O(rows + cols) per matrix instead of O(rows·cols),
# which is what lets deepseek-v3/kimi-k2 train states fit 16GB/chip
# (EXPERIMENTS.md §Dry-run memory table).
# ---------------------------------------------------------------------------

import dataclasses as _dc


@_dc.dataclass(frozen=True)
class AdafactorConfig:
    lr: float | Callable[[jax.Array], jax.Array] = 1e-2
    decay: float = 0.8  # beta2_t = 1 - step^-decay
    eps: float = 1e-30
    clip_threshold: float = 1.0
    weight_decay: float = 0.0
    min_dim_size_to_factor: int = 64


def _factored(shape, cfg: AdafactorConfig) -> bool:
    return (len(shape) >= 2 and shape[-1] >= cfg.min_dim_size_to_factor
            and shape[-2] >= cfg.min_dim_size_to_factor)


def adafactor_init(params: PyTree, cfg: AdafactorConfig) -> dict:
    def mk(p):
        if _factored(p.shape, cfg):
            return {
                "vr": jnp.zeros(p.shape[:-1], jnp.float32),  # row stats
                "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),  # col stats
            }
        return {"v": jnp.zeros(p.shape, jnp.float32)}

    return {"step": jnp.zeros((), jnp.int32),
            "v": jax.tree.map(mk, params, is_leaf=lambda x: hasattr(x, "shape"))}


def adafactor_update(grads: PyTree, state: dict, params: PyTree, cfg: AdafactorConfig):
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    beta2 = 1.0 - t ** (-cfg.decay)
    lr = cfg.lr(step) if callable(cfg.lr) else cfg.lr

    def upd(g, v, p):
        g32 = g.astype(jnp.float32)
        g2 = jnp.square(g32) + cfg.eps
        if "vr" in v:
            vr = beta2 * v["vr"] + (1 - beta2) * jnp.mean(g2, axis=-1)
            vc = beta2 * v["vc"] + (1 - beta2) * jnp.mean(g2, axis=-2)
            r_factor = jax.lax.rsqrt(
                vr / jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True), cfg.eps))
            c_factor = jax.lax.rsqrt(vc)
            u = g32 * r_factor[..., None] * c_factor[..., None, :]
            newv = {"vr": vr, "vc": vc}
        else:
            vv = beta2 * v["v"] + (1 - beta2) * g2
            u = g32 * jax.lax.rsqrt(vv)
            newv = {"v": vv}
        # update clipping by RMS
        rms_u = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-12)
        u = u / jnp.maximum(1.0, rms_u / cfg.clip_threshold)
        newp = p.astype(jnp.float32) - lr * u
        if cfg.weight_decay:
            newp = newp - lr * cfg.weight_decay * p.astype(jnp.float32)
        return newp.astype(p.dtype), newv

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_v = treedef.flatten_up_to(state["v"])
    outs = [upd(g, v, p) for g, v, p in zip(flat_g, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in outs])
    new_v = treedef.unflatten([o[1] for o in outs])
    return new_p, {"step": step, "v": new_v}
