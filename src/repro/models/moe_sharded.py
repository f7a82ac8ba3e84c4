"""Scatter-based expert-parallel MoE (shard_map) — the §Perf B7 dispatch.

The einsum (GShard) dispatch in moe.py builds (B,S,E,C) one-hot tensors and
pays O(T·E·C·D) FLOPs — 1-3× the expert compute itself. This version uses
the device-local formulation instead:

  * tokens are data-sharded and REPLICATED across `model` (the TP layout the
    rest of the block already uses), so every model rank sees its data
    shard's tokens and computes identical routing;
  * each model rank owns E/|model| experts (weights FSDP-sharded over
    `data`, all-gathered per layer inside the map — ZeRO-3);
  * rank-local scatter-add builds (E_loc, C, D) expert inputs in O(T·k·D);
  * expert FFN; gather back per assignment; psum over `model` combines the
    per-rank partial outputs.

Collectives per layer: the FSDP weight gather + one psum of (T_local, D) —
no dispatch-tensor resharding at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import nn
from repro.configs.base import ModelConfig
from repro.models.ffn import ffn_apply
from repro.models.moe import group_capacity, router_topk


def moe_apply_sharded(p, cfg: ModelConfig, x: jax.Array, *, batch_axes,
                      model_axis: str = "model", mesh=None):
    """Drop-in for moe.moe_apply under an active mesh. x (B, S, D)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    n_model = mesh.shape[model_axis]
    assert e % n_model == 0, (e, n_model)
    e_loc = e // n_model
    # per-shard token count decides capacity: tokens of one data shard
    n_data = 1
    for a in (batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)):
        n_data *= mesh.shape[a]
    t_shard = max(1, (b // max(1, n_data)) * s)
    cap = max(4, group_capacity(t_shard, cfg))  # per expert, per data shard

    from jax.sharding import PartitionSpec as P
    bax = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
    bspec = bax if len(bax) > 1 else bax[0]

    def local(xl, router, w_up, w_gate, w_down):
        # xl: (B_l, S, D) — identical across model ranks of a data shard
        bl = xl.shape[0]
        t = bl * s
        xt = xl.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router  # (T, E) — replicated compute
        top_w, top_i, probs = router_topk(logits, k)  # (T, k)

        # FSDP: assemble this rank's experts' full weights
        w_up = jax.lax.all_gather(w_up, bax, axis=1, tiled=True)
        w_gate = jax.lax.all_gather(w_gate, bax, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, bax, axis=2, tiled=True)

        # positions within each expert (consistent across ranks)
        pos_list, keep_list = [], []
        counts = jnp.zeros((e,), jnp.int32)
        for j in range(k):
            onehot_j = jax.nn.one_hot(top_i[:, j], e, dtype=jnp.int32)
            pos_j = jnp.cumsum(onehot_j, axis=0) - 1 + counts[None, :]
            counts = counts + jnp.sum(onehot_j, axis=0)
            pos_list.append(jnp.sum(pos_j * onehot_j, axis=1))
            keep_list.append(pos_list[-1] < cap)
        pos = jnp.stack(pos_list, 1)  # (T, k)
        keep = jnp.stack(keep_list, 1)

        rank = jax.lax.axis_index(model_axis)
        e0 = rank * e_loc
        mine = (top_i >= e0) & (top_i < e0 + e_loc) & keep  # (T, k)
        e_local = jnp.where(mine, top_i - e0, e_loc)  # e_loc = drop bucket
        pos_c = jnp.where(mine, pos, cap)  # cap = drop bucket

        # scatter-add tokens into (E_loc+1, C+1, D); last slices are drop bins
        buf = jnp.zeros((e_loc + 1, cap + 1, d), xl.dtype)
        tok_rep = jnp.repeat(xt[:, None, :], k, axis=1).reshape(t * k, d)
        idx = jnp.stack([e_local.reshape(-1), pos_c.reshape(-1)], axis=-1)
        buf = buf.at[idx[:, 0], idx[:, 1]].add(tok_rep)
        expert_in = buf[:e_loc, :cap]

        up = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
        gate = nn.act_fn(cfg.ffn_act)(jnp.einsum("ecd,edf->ecf", expert_in,
                                                 w_gate))
        expert_out = jnp.einsum("ecf,efd->ecd", gate * up, w_down)
        expert_out = jnp.pad(expert_out, ((0, 1), (0, 1), (0, 0)))

        # gather each assignment's output, weight, and sum over k
        out_rows = expert_out[e_local.reshape(-1), pos_c.reshape(-1)]
        out_rows = out_rows.reshape(t, k, d)
        w_eff = (top_w * mine.astype(jnp.float32)).astype(xl.dtype)
        y = jnp.einsum("tkd,tk->td", out_rows, w_eff)
        y = jax.lax.psum(y, model_axis)  # combine across expert ranks
        drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
        return y.reshape(bl, s, d), probs, top_i, drop_frac

    y, probs, top_i, drop = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None), P(), P(model_axis, bspec, None),
                  P(model_axis, bspec, None), P(model_axis, None, bspec)),
        out_specs=(P(bspec, None, None), P(bspec, None), P(bspec, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_up"], p["w_gate"], p["w_down"])

    if cfg.n_shared_experts:
        y = y + ffn_apply(p["shared"], x, cfg.ffn_act)
    e_arr = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    assign = jax.nn.one_hot(top_i[:, 0], e_arr, dtype=jnp.float32)
    ce = jnp.mean(assign, axis=0)
    aux = {"moe_lb_loss": e_arr * jnp.sum(me * ce),
           "moe_z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(
               jnp.log(jnp.maximum(probs, 1e-20)), axis=-1))),
           "moe_drop_frac": drop}
    return y, aux
