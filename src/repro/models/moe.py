"""Mixture-of-Experts FFN: dense dispatch (small scale) + expert-parallel
scatter dispatch (production scale).

Two dispatch paths with identical routing semantics:

  * moe_forward     — dense one-hot dispatch/combine einsums.  MXU-friendly
    and exactly testable, but materializes a (T, E, capacity) routing tensor
    whose size grows ~T^2: perfect for <=8-expert smoke configs, prohibitive
    at 128-160 experts x 131k tokens (would be >100 TB — EXPERIMENTS §Perf).
  * moe_forward_ep  — production path: `shard_map` over the mesh, tokens
    scatter-added into per-expert capacity buffers with *local* capacity,
    `lax.all_to_all` over the expert(=data) axis to the owning shards,
    expert FFN tensor-sharded over the inner axes, all_to_all back, gather
    combine.  O(T*k*d) memory, no (T,E,cap) tensor.  This is the GShard/
    DeepSpeed-MoE schedule with EP sharing the DP axis.

Both support: top-k routing with renormalized gates + load-balance & z
losses, shared (always-on) experts (DeepSeek-V2), and a parallel dense
residual FFN branch (Arctic).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init, mlp_forward


def init_moe(key, cfg, dtype=jnp.float32):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 8)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),  # router kept fp32
        "w_gate": jax.random.normal(ks[1], (E, d, ff), dtype) / math.sqrt(d),
        "w_up": jax.random.normal(ks[2], (E, d, ff), dtype) / math.sqrt(d),
        "w_down": jax.random.normal(ks[3], (E, ff, d), dtype) / math.sqrt(ff),
    }
    if cfg.num_shared_experts:
        sf = ff * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks[4], d, sf, dtype),
            "w_up": dense_init(ks[5], d, sf, dtype),
            "w_down": dense_init(ks[6], sf, d, dtype),
        }
    if cfg.moe_dense_residual:
        from .layers import init_mlp
        p["dense_res"] = init_mlp(ks[7], d, cfg.dense_ff, dtype)
    return p


def moe_forward(p, x, cfg) -> Tuple[jnp.ndarray, dict]:
    """x: (B, S, d) -> (y, aux) with aux = {load_balance_loss, router_z_loss}."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)

    logits = xt.astype(jnp.float32) @ p["router"]            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # (T, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    # capacity per expert
    cap = max(int(math.ceil(T * k / E * cfg.capacity_factor)), 1)

    # (T, k, E) one-hot of chosen experts
    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    # position of each (token, choice) within its expert queue
    # flatten choices in priority order: all k=0 choices first
    sel_f = sel.transpose(1, 0, 2).reshape(k * T, E)          # (kT, E)
    pos_f = jnp.cumsum(sel_f, axis=0) - sel_f                 # (kT, E)
    pos = pos_f.reshape(k, T, E).transpose(1, 0, 2)           # (T, k, E)
    keep = (pos < cap) * sel                                  # dropped past capacity
    pos = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)      # (T, k)

    # dispatch tensor (T, E, cap)
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)      # (T, k, cap)
    disp = jnp.einsum("tke,tkc->tec", keep, pos_oh)           # (T, E, cap)
    comb = jnp.einsum("tke,tk,tkc->tec", keep, gate_vals, pos_oh)

    exp_in = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), xt)   # (E, cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", exp_in, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", exp_in, p["w_up"])
    exp_out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])           # (E, cap, d)
    y = jnp.einsum("tec,ecd->td", comb.astype(x.dtype), exp_out)   # (T, d)

    if "shared" in p:
        y = y + mlp_forward(p["shared"], xt)
    if "dense_res" in p:
        y = y + mlp_forward(p["dense_res"], xt)

    # aux losses (Switch-style)
    frac_tokens = jnp.mean(sel.sum(1), axis=0)                # (E,) f_i
    frac_probs = jnp.mean(probs, axis=0)                      # (E,) p_i
    lb_loss = E * jnp.sum(frac_tokens * frac_probs)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"load_balance_loss": lb_loss, "router_z_loss": z_loss}
    return y.reshape(B, S, d), aux


# ======================================================================
# expert-parallel production path
# ======================================================================

def _route(logits, k):
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    return probs, gate_vals, gate_idx


def _queue_positions(gate_idx, E):
    """Position of each (token, choice) within its expert's queue — cumsum
    over a (T*k, E) one-hot, priority order = all first choices first."""
    T, k = gate_idx.shape
    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)       # (T, k, E)
    sel_f = sel.transpose(1, 0, 2).reshape(k * T, E)
    pos_f = jnp.cumsum(sel_f, axis=0) - sel_f
    pos = pos_f.reshape(k, T, E).transpose(1, 0, 2)
    pos = jnp.sum(pos * sel, axis=-1).astype(jnp.int32)        # (T, k)
    return pos, sel


def _ep_body(x, router, w_gate, w_up, w_down, *, cfg, ep_axis, inner_axes,
             batch_ax):
    """Per-shard body under shard_map.

    x: (B_loc, S, d) local tokens (replicated over the inner axes);
    w_*: (E_loc, d, ff_loc) local expert shards.  Returns (y_loc, lb, z)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    ep = jax.lax.axis_size(ep_axis)
    T = B * S
    xt = x.reshape(T, d)

    logits = xt.astype(jnp.float32) @ router                    # (T, E)
    probs, gate_vals, gate_idx = _route(logits, k)
    cap = max(int(math.ceil(T * k / E * cfg.capacity_factor)), 1)

    pos, sel = _queue_positions(gate_idx, E)
    keep = pos < cap                                            # (T, k) bool
    flat_idx = jnp.where(keep, gate_idx * cap + pos, E * cap)   # drop slot

    # scatter dispatch into (E*cap + 1, d); the +1 row swallows drops
    buf = jnp.zeros((E * cap + 1, d), x.dtype)
    for i in range(k):
        buf = buf.at[flat_idx[:, i]].add(xt)
    buf = buf[:E * cap].reshape(E, cap, d)

    # all-to-all: send each expert's queue to its owning shard
    # (E, cap, d) -> (E/ep, ep*cap, d)
    buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                             tiled=True)

    # expert FFN, ff sharded over the inner axes -> psum completes d
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up)
    out = jnp.einsum("ecf,efd->ecd", h, w_down)
    if inner_axes:
        out = jax.lax.psum(out, inner_axes)

    # return the computed queues to the token shards
    out = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)                        # (E, cap, d)
    out = jnp.concatenate(
        [out.reshape(E * cap, d), jnp.zeros((1, d), out.dtype)], axis=0)

    y = jnp.zeros((T, d), x.dtype)
    for i in range(k):
        contrib = out[flat_idx[:, i]] * gate_vals[:, i, None].astype(out.dtype)
        y = y + jnp.where(keep[:, i, None], contrib, 0.0).astype(x.dtype)

    # aux losses need GLOBAL token fractions: pmean f_i and p_i over the
    # batch shards BEFORE the (nonlinear) product — local-then-average
    # differs whenever shards are imbalanced
    frac_tokens = jax.lax.pmean(jnp.mean(sel.sum(1), axis=0), batch_ax)
    frac_probs = jax.lax.pmean(jnp.mean(probs, axis=0), batch_ax)
    lb = E * jnp.sum(frac_tokens * frac_probs)
    z = jax.lax.pmean(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
                      batch_ax)
    return y.reshape(B, S, d), lb, z


def moe_forward_ep(p, x, cfg, *, mesh, batch_ax=("data",), ep_axis="data",
                   inner_axes=("attn", "ffn")) -> Tuple[jnp.ndarray, dict]:
    """Expert-parallel MoE layer (see module docstring).

    Shared experts / the dense residual run at pjit level (plain
    tensor-parallel MLPs over all tokens); only routed experts enter the
    shard_map."""
    inner_axes = tuple(a for a in inner_axes if a in mesh.axis_names
                       and mesh.shape[a] > 1)
    rep_axes = tuple(a for a in mesh.axis_names
                     if a not in (ep_axis,) + tuple(batch_ax))

    body = partial(_ep_body, cfg=cfg, ep_axis=ep_axis,
                   inner_axes=inner_axes, batch_ax=batch_ax)

    ff_spec = P(ep_axis, None, inner_axes or None)
    down_spec = P(ep_axis, inner_axes or None, None)
    x_spec = P(batch_ax, None, None)

    y, lb, z = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), ff_spec, ff_spec, down_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        y = y + mlp_forward(p["shared"], x)
    if "dense_res" in p:
        y = y + mlp_forward(p["dense_res"], x)
    aux = {"load_balance_loss": lb, "router_z_loss": z}
    return y, aux
