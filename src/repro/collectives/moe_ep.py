"""Expert-parallel MoE via shard_map all-to-all — the §Perf replacement
for the GShard-style dense-dispatch einsums (models/moe.py).

Why: the einsum path's dispatch/combine tensors add O(T*E*C*D) HLO FLOPs
and giant intermediates (granite train_4k baseline: useful-FLOPs ratio
0.137, collective term 37 s).  The EP path routes tokens with a LOCAL
scatter (O(T*D)), exchanges only real token payloads with all-to-all over
the expert-parallel axis, and runs dense per-expert matmuls — the MoE
communication pattern the paper's alltoall analysis is about, with the
DIRECT vs HIERARCHICAL schedule choice (Algorithm 1) applied to the a2a.

Requires n_experts % ep_size == 0 (the hillclimb pairs granite/qwen2-moe
with a (64, 4) mesh: 40 % 4 == 0, 60 % 4 == 0).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.collectives.modes import CollectiveMode
from repro.models.common import ModelConfig, activation, dp_spec, mesh_axes
from repro.models.mlp import mlp


def _local_dispatch(x, probs, cfg: ModelConfig, capacity: int):
    """Local top-k -> per-expert buckets.

    x: [T, D]; probs: [T, E].  Returns (buffer [E, C, D], gates [T, k],
    expert_idx [T, k], slot_idx [T, k], aux)."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    topv, topi = jax.lax.top_k(probs, k)                  # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    counts = jnp.zeros((E,), jnp.int32)
    buffer = jnp.zeros((E, capacity, D), x.dtype)
    slots = []
    for j in range(k):                                    # k <= 8
        e = topi[:, j]                                    # [T]
        oh = jax.nn.one_hot(e, E, dtype=jnp.int32)        # [T, E]
        pos = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(T), e] + counts[e]
        keep = pos < capacity
        slot = jnp.where(keep, pos, capacity)             # OOB -> dropped
        buffer = buffer.at[e, slot.clip(0, capacity - 1)].add(
            jnp.where(keep[:, None], x, 0).astype(x.dtype))
        slots.append(jnp.where(keep, slot, -1))
        counts = counts + oh.sum(axis=0)
    me = probs.mean(axis=0)
    top1 = jax.nn.one_hot(topi[:, 0], E).mean(axis=0)
    aux = E * jnp.sum(me * top1)
    return buffer, topv, topi, jnp.stack(slots, 1), aux


def _expert_ffn(p, xe, cfg: ModelConfig):
    """xe: [E_local, C_all, D] -> same; dense per-expert matmuls."""
    dt = cfg.dtype
    h = jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dt))
    h = activation(g, cfg.act) * h
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(dt))


def moe_ep(p, x, cfg: ModelConfig, *,
           mode: CollectiveMode = CollectiveMode.DIRECT,
           ep_axis: str = "model", capacity_factor: float = 1.25):
    """Drop-in replacement for models.moe.moe_einsum (x: [B,S,D]).

    Must run under jit with an active mesh whose `ep_axis` divides
    n_experts.  Expert weights are expected EP-sharded ([E, D, F] with E
    over ep_axis — sharding/partition.py's rule)."""
    axes = mesh_axes()
    ep = axes.get(ep_axis, 1)
    assert cfg.n_experts % max(ep, 1) == 0, (cfg.n_experts, ep)
    B, S, D = x.shape
    dp = dp_spec()
    from jax.sharding import PartitionSpec as P

    E, k = cfg.n_experts, cfg.top_k
    mesh = jax.sharding.get_abstract_mesh()
    dp_tuple = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    n_dp = 1
    for a in dp_tuple:
        n_dp *= axes[a]
    T_loc = (B // max(n_dp, 1)) * S
    capacity = max(k, int(math.ceil(T_loc * k * capacity_factor / E)))

    def body(xl, router_w, w_in, w_gate, w_out, shared):
        # xl: [B/n_dp, S, D] (replicated over ep_axis); experts local E/ep
        Bl = xl.shape[0]
        xt = xl.reshape(-1, D)
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", xt.astype(jnp.float32), router_w), -1)
        buf, gates, eidx, slots, aux = _local_dispatch(xt, probs, cfg,
                                                       capacity)
        # [E, C, D] -> a2a -> [E/ep * ep? ...]: send expert-major shards
        if mode == CollectiveMode.HIERARCHICAL and "pod" in axes:
            from repro.collectives.alltoall import alltoall_hierarchical
            recv = alltoall_hierarchical(buf, "pod", ep_axis)
        else:
            recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                      concat_axis=0, tiled=True)
        # recv: [E? -> (ep * E_local), C, D] grouped as [ep, E_local, C, D]
        E_loc = E // ep
        recv = recv.reshape(ep, E_loc, capacity, D) \
            .transpose(1, 0, 2, 3).reshape(E_loc, ep * capacity, D)
        out = _expert_ffn({"w_in": w_in, "w_gate": w_gate,
                           "w_out": w_out}, recv, cfg)
        out = out.reshape(E_loc, ep, capacity, D).transpose(1, 0, 2, 3) \
            .reshape(E, capacity, D)
        if mode == CollectiveMode.HIERARCHICAL and "pod" in axes:
            from repro.collectives.alltoall import alltoall_hierarchical
            back = alltoall_hierarchical(out, "pod", ep_axis)
        else:
            back = jax.lax.all_to_all(out, ep_axis, split_axis=0,
                                      concat_axis=0, tiled=True)
        # combine: gather each (token, choice) slot, weight by gate
        y = jnp.zeros_like(xt)
        for j in range(k):
            slot = slots[:, j]
            val = back[eidx[:, j], slot.clip(0, capacity - 1)]
            val = jnp.where((slot >= 0)[:, None], val, 0)
            y = y + gates[:, j][:, None].astype(val.dtype) * val
        y = y.reshape(Bl, S, D)
        aux = jax.lax.pmean(aux, dp_tuple + (ep_axis,)) \
            if (dp_tuple or ep) else aux
        return y, aux

    w = p  # param dict
    E_loc = E // ep
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp if dp else None, None, None), P(), P(ep_axis),
                  P(ep_axis), P(ep_axis), P()),
        out_specs=(P(dp if dp else None, None, None), P()),
        check_vma=False,
    )(x, w["router"], w["w_in"], w["w_gate"], w["w_out"], 0)
    if cfg.n_shared_experts:
        y = y + mlp(w["shared"], x, cfg)
    return y, aux.astype(jnp.float32)


def moe_ep_ref(p, x, cfg: ModelConfig, capacity_factor: float = 1.25):
    """Single-device oracle: same dispatch math, no collectives."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    E, k = cfg.n_experts, cfg.top_k
    capacity = max(k, int(math.ceil(T * k * capacity_factor / E)))
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"]), -1)
    buf, gates, eidx, slots, aux = _local_dispatch(xt, probs, cfg, capacity)
    out = _expert_ffn(p, buf, cfg)
    y = jnp.zeros_like(xt)
    for j in range(k):
        slot = slots[:, j]
        val = out[eidx[:, j], slot.clip(0, capacity - 1)]
        val = jnp.where((slot >= 0)[:, None], val, 0)
        y = y + gates[:, j][:, None].astype(val.dtype) * val
    y = y.reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x, cfg)
    return y, aux.astype(jnp.float32)
