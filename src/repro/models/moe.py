"""Mixture-of-Experts layer with an explicit shard_map collective schedule.

Design (DESIGN.md §3): tokens stay sharded over the ("pod","data") axes and
are *replicated* over the "model" axis (they already are, in the standard
TP layout).  Expert placement depends on the expert count:

  - ``E % model_size == 0``  (kimi, 384 experts): each model rank owns
    ``E/16`` experts with full d_ff — classic expert parallelism.  A rank
    dispatches only the token-slots routed to *its* experts.
  - otherwise (grok, 8 experts): every rank holds an ``f/16`` slice of every
    expert (tensor parallelism inside the expert); each rank processes *all*
    routed slots on its slice.

Either way each (token, expert) slot's FLOPs are computed exactly once
across the mesh and the only collective is ONE ``psum`` over "model" per MoE
layer, combining the partial d_model outputs.  No (N,E,C) one-hot dispatch
tensor is ever materialized — dispatch is a capacity-bounded scatter-add,
combine is a gather, both rank-local.

Without a mesh (smoke tests / single device) the same math runs locally.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.models.layers import cdt, he, pdt


def init_moe(key, cfg):
    m = cfg.moe
    E, d, f = m.num_experts, cfg.d_model, m.d_ff
    ks = jax.random.split(key, 4)
    dt = pdt(cfg)
    return {
        "router": he(ks[0], (d, E), jnp.float32),
        "w_gate": he(ks[1], (E, d, f), dt, fan_in=d),
        "w_up": he(ks[2], (E, d, f), dt, fan_in=d),
        "w_down": he(ks[3], (E, f, d), dt, fan_in=f),
    }


def spec_moe(cfg):
    # Claiming rule resolves ("model", ..., "model") to expert- or
    # tensor-sharding depending on divisibility (see repro.sharding).
    return {
        "router": (None, None),
        "w_gate": ("model", "fsdp", "model"),
        "w_up": ("model", "fsdp", "model"),
        "w_down": ("model", "model", "fsdp"),
    }


def _capacity(n_tokens: int, cfg) -> int:
    m = cfg.moe
    c = int(math.ceil(m.experts_per_token * n_tokens * m.capacity_factor
                      / m.num_experts))
    return max(8, (c + 7) // 8 * 8)


def _route(router_w, cfg, x32):
    """x32: (N, d) fp32 -> topk ids (N,k) int32, weights (N,k) fp32."""
    logits = x32 @ router_w  # (N, E)
    top_logits, top_ids = jax.lax.top_k(logits, cfg.moe.experts_per_token)
    weights = jax.nn.softmax(top_logits, axis=-1)
    return top_ids, weights


def _expert_mlp(cfg, xb, wg, wu, wd):
    """xb: (E_loc, C, d); weights (E_loc, d, f_loc)/(E_loc, f_loc, d)."""
    act = jax.nn.silu if cfg.mlp == "swiglu" else partial(jax.nn.gelu, approximate=True)
    h = act(jnp.einsum("ecd,edf->ecf", xb, wg)) * jnp.einsum("ecd,edf->ecf", xb, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _moe_block(cfg, x, router_w, wg, wu, wd, *, e_start, e_count, n_model):
    """Process one rank's share.  x: (N_loc, d) full tokens;
    weights are this rank's blocks; experts [e_start, e_start+e_count) are
    dispatched here (tensor mode passes the full range).
    Returns the rank's partial output (N_loc, d)."""
    ct = cdt(cfg)
    N, d = x.shape
    k = cfg.moe.experts_per_token
    C = _capacity(N, cfg)

    top_ids, top_w = _route(router_w.astype(jnp.float32), cfg, x.astype(jnp.float32))
    flat_e = top_ids.reshape(-1)  # (N*k,)
    local = (flat_e >= e_start) & (flat_e < e_start + e_count)
    loc_e = jnp.clip(flat_e - e_start, 0, e_count - 1)

    # position of each slot within its expert's capacity buffer
    onehot = (jax.nn.one_hot(loc_e, e_count, dtype=jnp.int32)
              * local[:, None].astype(jnp.int32))  # (N*k, e_count)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    slot_pos = jnp.take_along_axis(pos, loc_e[:, None], axis=1)[:, 0]
    keep = local & (slot_pos < C)
    flat_idx = jnp.where(keep, loc_e * C + slot_pos, e_count * C)  # OOB -> dropped

    xs = jnp.repeat(x.astype(ct), k, axis=0)  # (N*k, d)
    buf = jnp.zeros((e_count * C + 1, d), ct).at[flat_idx].add(
        xs * keep[:, None].astype(ct), mode="drop")
    buf = buf[:-1].reshape(e_count, C, d)

    out_buf = _expert_mlp(cfg, buf, wg.astype(ct), wu.astype(ct), wd.astype(ct))

    gathered = out_buf.reshape(e_count * C, d)[jnp.clip(flat_idx, 0, e_count * C - 1)]
    gathered = gathered * (keep[:, None] * top_w.reshape(-1)[:, None]).astype(ct)
    return gathered.reshape(N, k, d).sum(axis=1)


def _moe_sharded(cfg, expert_mode, n_model, fsdp_axes, x, router_w, wg, wu, wd):
    """Body run under shard_map over the full mesh.

    FSDP all-gather of the expert weights happens HERE, explicitly, rather
    than at the shard_map boundary: ``jax.lax.all_gather`` differentiates to
    ``psum_scatter``, so the weight-gradient combine is a reduce-scatter in
    the weights' own (bf16) dtype — vs. the full-size fp32 all-reduce the
    SPMD partitioner emits for a boundary reshard (measured 4x collective
    bytes on kimi's 2 TB of expert weights; EXPERIMENTS.md §Perf)."""
    if fsdp_axes:
        # optimization_barrier pins the collectives to the params' bf16
        # dtype: without it the CPU pipeline hoists its dot-promotion
        # f32 converts above the gather, doubling the modelled ICI bytes
        wg = jax.lax.optimization_barrier(
            jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True))
        wu = jax.lax.optimization_barrier(
            jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True))
        wd = jax.lax.optimization_barrier(
            jax.lax.all_gather(wd, fsdp_axes, axis=2, tiled=True))
    if expert_mode:
        rank = jax.lax.axis_index("model")
        e_count = cfg.moe.num_experts // n_model
        y = _moe_block(cfg, x, router_w, wg, wu, wd,
                       e_start=rank * e_count, e_count=e_count, n_model=n_model)
    else:  # tensor mode: all experts, f-sliced weights
        y = _moe_block(cfg, x, router_w, wg, wu, wd,
                       e_start=0, e_count=cfg.moe.num_experts, n_model=n_model)
    # cast before the combine so the collective moves compute-dtype bytes
    # (barrier stops the convert being hoisted past the psum)
    return jax.lax.psum(jax.lax.optimization_barrier(y.astype(cdt(cfg))),
                        "model")


def apply_moe(p, cfg, x):
    """x: (B, T, d) -> (B, T, d)."""
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    mesh = runtime.get_mesh()
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        y = _moe_block(cfg, xf, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                       e_start=0, e_count=cfg.moe.num_experts, n_model=1)
        return y.reshape(B, T, d)

    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    expert_mode = cfg.moe.num_experts % n_model == 0
    dp = runtime.data_axes(mesh)
    # Under FSDP the weights enter the shard_map still d_model-sharded over
    # the data axes and are all-gathered *inside* (see _moe_sharded); the
    # divisibility guard mirrors repro.sharding.resolve_spec.
    fsdp_axes = dp if (cfg.fsdp and dp and
                       cfg.d_model % int(np.prod([mesh.shape[a] for a in dp]))
                       == 0) else ()
    fs = dp if fsdp_axes else None
    if expert_mode:
        w_spec = (P("model", fs, None), P("model", fs, None),
                  P("model", None, fs))
    else:
        w_spec = (P(None, fs, "model"), P(None, fs, "model"),
                  P(None, "model", fs))

    fn = jax.shard_map(
        partial(_moe_sharded, cfg, expert_mode, n_model, tuple(fsdp_axes)),
        mesh=mesh,
        in_specs=(P(dp, None), P(None, None)) + w_spec,
        out_specs=P(dp, None),
        check_vma=False,
    )
    y = fn(xf, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y.reshape(B, T, d)


def active_fraction(cfg) -> float:
    """Fraction of expert params active per token (for MODEL_FLOPS)."""
    m = cfg.moe
    return m.experts_per_token / m.num_experts
