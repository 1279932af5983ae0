"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Switch/Mixtral-style: tokens are routed to their top-k experts; each expert
processes at most ``capacity`` tokens (overflow dropped — standard for
TPU-shape-static MoE). Dispatch/combine use scatter/gather rather than the
dense one-hot einsum so compiled FLOPs stay ~(top_k * capacity_factor) x the
dense-FFN cost — the roofline then reflects the real MoE arithmetic, and the
expert dimension shards over the 'model' mesh axis (expert parallelism).

An auxiliary load-balance loss (Shazeer-style: E * sum_e f_e * p_e) is
returned so training discourages expert collapse.
"""
from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.layers import _he
from repro.utils import hints


def init_moe(key, d_model: int, d_ff: int, num_experts: int, dtype=jnp.float32):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": _he(kr, (d_model, num_experts), jnp.float32, fan_in=d_model),
        "w_gate": _he(k1, (num_experts, d_model, d_ff), dtype, fan_in=d_model),
        "w_up": _he(k2, (num_experts, d_model, d_ff), dtype, fan_in=d_model),
        "w_down": _he(k3, (num_experts, d_ff, d_model), dtype, fan_in=d_ff),
    }


def moe_ffn(
    params,
    x: jax.Array,                 # (B, S, d_model)
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,d), aux load-balance loss scalar).

    Distribution (the hard part, learned by measurement — §Perf):
    scatter/gather-based dispatch lowers to HLO scatter with iota-
    concatenated indices, which GSPMD cannot partition on the (data-
    sharded) batch axis — it replicates the (B,E,C,d) dispatch buffers and
    all-gathers them every layer (measured 43GB/layer on mixtral train_4k).
    When a mesh is active (hints.active()), we therefore run the whole
    dispatch→expert-FFN→combine path inside a *partial-auto shard_map*:
    the data/pod axes are manual (each shard dispatches its own tokens —
    zero dispatch collectives, the paper-faithful "local routing" of
    group-wise MoE), while the model axis stays auto so the expert einsums
    keep their tensor-parallel sharding (w_down partials psum over model).
    Weight gradients get the data-axis psum from shard_map's autodiff.

    Dispatch is GROUP-WISE (group = one batch row): position-in-expert is
    a cumsum over the sequence axis only; capacity is per group.
    """
    mode = os.environ.get("REPRO_MOE_DISPATCH", "sharded")
    if hints.active() and mode == "sharded":
        # batch must divide the data axes (long_500k decodes batch=1 —
        # a 1-token FFN is trivially local, plain SPMD handles it fine)
        mesh = hints.get_mesh()
        dsize = 1
        for ax in hints.get_batch_axes():
            dsize *= mesh.shape[ax]
        if x.shape[0] % dsize == 0:
            return _moe_manual(params, x, num_experts=num_experts,
                               top_k=top_k, capacity_factor=capacity_factor)
    if mode == "global":        # §Perf baseline: global-token-axis dispatch
        return _moe_global(params, x, num_experts=num_experts, top_k=top_k,
                           capacity_factor=capacity_factor)
    return _moe_local(params, x, num_experts=num_experts, top_k=top_k,
                      capacity_factor=capacity_factor)


def _moe_global(params, x, *, num_experts, top_k, capacity_factor):
    """The naive formulation kept for §Perf A/B: position-in-expert from a
    cumsum over the GLOBAL flattened token axis. Semantically fine, but the
    cross-shard cumsum + unbatchable scatter replicate the dispatch buffers
    under SPMD (the measured collective/memory catastrophe)."""
    b, s, d = x.shape
    n = b * s
    xt = x.reshape(n, d)
    logits = (xt.astype(jnp.float32) @ params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    capacity = max(1, int(capacity_factor * n * top_k / num_experts))
    out = jnp.zeros((n, d), jnp.float32)
    aux_f = jnp.zeros((num_experts,), jnp.float32)
    for slot in range(top_k):
        eid = expert_ids[:, slot]
        gv = gate_vals[:, slot]
        onehot = jax.nn.one_hot(eid, num_experts, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
        keep = pos < capacity
        aux_f = aux_f + jnp.sum(onehot, axis=0).astype(jnp.float32)
        safe_e = jnp.where(keep, eid, 0)
        safe_p = jnp.where(keep, pos, capacity)
        buf = jnp.zeros((num_experts, capacity + 1, d), x.dtype)
        buf = buf.at[safe_e, safe_p].set(xt)
        h = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, params["w_down"])
        gathered = y[safe_e, safe_p]
        out = out + jnp.where(keep[:, None], gathered.astype(jnp.float32),
                              0.0) * gv[:, None]
    frac = aux_f / jnp.maximum(aux_f.sum(), 1.0)
    aux = num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return out.reshape(b, s, d).astype(x.dtype), aux


def _moe_manual(params, x, *, num_experts, top_k, capacity_factor,
                model_axis: str = "model"):
    """Fully-manual shard_map MoE: explicit expert/tensor parallelism.

    E >= |model axis|  -> expert parallelism: each model shard owns E/m
        experts, computes only its experts' tokens (foreign tokens combine
        from zero rows), one psum over the model axis per layer.
    E <  |model axis|  -> tensor parallelism on d_ff: every shard holds all
        experts with an f-slice; w_down partials psum over the model axis.

    The data/pod axes are manual too: each shard dispatches only its own
    tokens (zero dispatch collectives). Weight cotangents pick up the
    data-axis psum from shard_map's transpose of the replicated in_spec.
    (A partial-auto shard_map — model axis left auto — trips an XLA CPU
    CHECK in AllReducePromotion; fully-manual sidesteps it. §Perf)
    """
    from jax.sharding import PartitionSpec as P
    mesh = hints.get_mesh()
    baxes = hints.get_batch_axes()
    model_n = mesh.shape[model_axis]
    expert_parallel = num_experts >= model_n
    if expert_parallel:
        wspec = {"router": P(), "w_gate": P(model_axis),
                 "w_up": P(model_axis), "w_down": P(model_axis)}
    else:
        wspec = {"router": P(), "w_gate": P(None, None, model_axis),
                 "w_up": P(None, None, model_axis),
                 "w_down": P(None, model_axis, None)}

    def local(p, xl):
        out, aux = _moe_local(
            p, xl, num_experts=num_experts, top_k=top_k,
            capacity_factor=capacity_factor,
            expert_parallel=(expert_parallel, model_axis, model_n))
        out = jax.lax.psum(out.astype(jnp.float32), model_axis)
        # per-shard scalar -> (1,); averaged outside the shard_map (an
        # in-body pmean trips the same XLA CPU CHECK)
        return out.astype(xl.dtype), aux[None]

    fn = shard_map(
        local, mesh=mesh, in_specs=(wspec, P(baxes)),
        out_specs=(P(baxes), P(baxes)), check_vma=False)
    out, aux_shards = fn(params, x)
    return out, jnp.mean(aux_shards)


def _moe_local(
    params,
    x: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float,
    expert_parallel=None,        # (enabled, model_axis, model_n) | None
) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    ep_on, ep_axis, ep_n = expert_parallel or (False, None, 1)
    e_loc = num_experts // ep_n if ep_on else num_experts

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])                        # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)          # (B, S, k)
    # renormalize the selected gates (Mixtral convention)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    capacity = max(1, int(capacity_factor * s * top_k / num_experts))

    def _slot(xg, eidg, gvg, posg, keepg):
        """One group's dispatch -> expert FFN -> combine (vmapped over the
        local batch). Under the mesh this runs inside the fully-manual
        shard_map (_moe_manual) so the scatter/gather never cross shards;
        see the module docstring and §Perf for why SPMD alone cannot
        partition this pattern."""
        safe_e = jnp.where(keepg, eidg, 0)
        safe_p = jnp.where(keepg, posg, capacity)        # trash slot
        buf = jnp.zeros((num_experts, capacity + 1, d), xg.dtype)
        buf = buf.at[safe_e, safe_p].set(xg)

        if ep_on:
            # expert parallelism: run only this shard's experts; foreign
            # tokens combine from the zero rows and the outer psum merges
            e0 = jax.lax.axis_index(ep_axis) * e_loc
            buf_my = jax.lax.dynamic_slice_in_dim(buf, e0, e_loc, 0)
        else:
            buf_my = buf

        h = jnp.einsum("ecd,edf->ecf", buf_my, params["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf_my, params["w_up"])
        act = jax.nn.silu(h) * u
        y_my = jnp.einsum("ecf,efd->ecd", act, params["w_down"])

        if ep_on:
            y = jnp.zeros((num_experts, capacity + 1, d), y_my.dtype)
            y = jax.lax.dynamic_update_slice_in_dim(y, y_my, e0, 0)
        else:
            y = y_my                                             # (E,C+1,d)

        gathered = y[safe_e, safe_p]                             # (S, d)
        return jnp.where(keepg[:, None],
                         gathered.astype(jnp.float32), 0.0) * gvg[:, None]

    out = jnp.zeros((b, s, d), jnp.float32)
    aux_f = jnp.zeros((num_experts,), jnp.float32)

    for slot in range(top_k):
        eid = expert_ids[..., slot]                              # (B, S)
        gv = gate_vals[..., slot]
        onehot = jax.nn.one_hot(eid, num_experts, dtype=jnp.int32)  # (B,S,E)
        pos_in_expert = jnp.cumsum(onehot, axis=1) - onehot         # per group
        pos = jnp.sum(pos_in_expert * onehot, axis=-1)              # (B, S)
        keep = pos < capacity
        aux_f = aux_f + jnp.sum(onehot, axis=(0, 1)).astype(jnp.float32)
        out = out + jax.vmap(_slot)(x, eid, gv, pos, keep)

    # load-balance aux loss: E * sum_e (fraction routed to e) * (mean prob e)
    frac = aux_f / jnp.maximum(aux_f.sum(), 1.0)
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = num_experts * jnp.sum(frac * mean_prob)
    return out.astype(x.dtype), aux
