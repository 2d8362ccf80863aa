"""Top-k MoE layer with capacity-bounded scatter/gather dispatch (EP-ready).

Dispatch is implemented with scatter/gather (not the GShard dense one-hot
einsum): routing builds an (expert, slot) table, tokens are scattered into
a (E, C, d) buffer, expert FFNs run as a batched einsum over the expert
axis (sharded over "model" = expert parallelism), and outputs gather back.
This keeps compiled HLO FLOPs equal to *useful* FLOPs — a dense dispatch
einsum would add O(tokens * E * C * d) fake FLOPs and wreck the roofline
accounting (see EXPERIMENTS.md).

Groups: each batch row is a routing group (G = B, S = T), so the
position-in-expert cumsum never crosses device boundaries under batch
sharding — no collectives inside routing.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                       # per-expert hidden
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    renormalize: bool = True        # renormalize top-k gates to sum to 1
    aux_weight: float = 0.01        # Switch/GShard load-balance loss weight
    router_z_weight: float = 0.0
    gated: bool = True              # SwiGLU experts
    n_layers_scale: int = 1


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    out_scale = 1.0 / np.sqrt(2.0 * max(cfg.n_layers_scale, 1))
    p = {
        "router": L.dense_init(ks[0], (d, e), dtype=jnp.float32),
        "wi": L.dense_init(ks[1], (e, d, f), dtype=dtype),
        "wo": L.dense_init(ks[2], (e, f, d), scale=out_scale, dtype=dtype),
    }
    if cfg.gated:
        p["wg"] = L.dense_init(ks[3], (e, d, f), dtype=dtype)
    return p


def capacity(cfg: MoEConfig, tokens_per_group: int) -> int:
    c = int(np.ceil(tokens_per_group * cfg.top_k * cfg.capacity_factor
                    / cfg.num_experts))
    return max(4, -(-c // 4) * 4)   # round up to a multiple of 4


def route(router_logits: jax.Array, cfg: MoEConfig, cap: int):
    """Token->slot assignment for one batch of groups.

    router_logits: (G, S, E) f32.
    Returns (slot (G, S*k) int32 [sentinel E*cap = dropped], gate (G, S, k),
             aux_loss scalar).
    """
    g_, s_, e_ = router_logits.shape
    k = cfg.top_k
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                  # (G, S, k)
    if cfg.renormalize:
        gate = gate / jnp.maximum(
            jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    eflat = eidx.reshape(g_, s_ * k)                      # (G, S*k)
    onehot = jax.nn.one_hot(eflat, e_, dtype=jnp.int32)   # (G, S*k, E)
    # position of each assignment within its expert queue (priority by
    # token order, then by routing rank — standard GShard tie-break)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot         # (G, S*k, E)
    pos = jnp.sum(pos_all * onehot, axis=-1)              # (G, S*k)
    keep = pos < cap
    slot = jnp.where(keep, eflat * cap + pos, e_ * cap)   # sentinel drops

    # load-balance aux (Switch eq.4 over all k assignments)
    frac_tokens = jnp.mean(onehot.astype(jnp.float32), axis=(0, 1)) * (e_ / k)
    frac_probs = jnp.mean(probs, axis=(0, 1)) * e_
    aux = jnp.sum(frac_tokens * frac_probs) / e_
    if cfg.router_z_weight > 0.0:
        zl = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
        aux = aux + cfg.router_z_weight / max(cfg.aux_weight, 1e-9) * zl
    return slot, gate, aux


def _dispatch_ffn_combine(params, x, slot, gate, cfg: MoEConfig, cap: int,
                          n_local_experts: int, expert_offset):
    """Scatter -> expert FFN -> gather for `n_local_experts` experts.

    slot carries GLOBAL slot ids (expert * cap + pos, sentinel E*cap);
    ids outside this shard's [offset*cap, (offset+n_local)*cap) window map
    to the local sentinel.  Runs unsharded when n_local == num_experts.
    """
    g_, s_, d = x.shape
    k = cfg.top_k
    lo = expert_offset * cap
    local_slot = slot - lo
    in_range = (local_slot >= 0) & (local_slot < n_local_experts * cap)
    local_slot = jnp.where(in_range, local_slot, n_local_experts * cap)

    xk = jnp.repeat(x, k, axis=1)                         # (G, S*k, d)
    gidx = jnp.arange(g_)[:, None]
    xe = jnp.zeros((g_, n_local_experts * cap + 1, d),
                   x.dtype).at[gidx, local_slot].add(xk)
    xe = xe[:, :n_local_experts * cap].reshape(
        g_, n_local_experts, cap, d)

    up = jnp.einsum("gecd,edf->gecf", xe, params["wi"])
    if cfg.gated:
        gg = jnp.einsum("gecd,edf->gecf", xe, params["wg"])
        act = jax.nn.silu(gg.astype(jnp.float32)).astype(x.dtype) * up
    else:
        act = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    ye = jnp.einsum("gecf,efd->gecd", act, params["wo"])

    ye_flat = jnp.concatenate(
        [ye.reshape(g_, n_local_experts * cap, d),
         jnp.zeros((g_, 1, d), ye.dtype)], axis=1)        # sentinel row
    out_k = jnp.take_along_axis(ye_flat, local_slot[..., None], axis=1)
    out = jnp.sum(
        out_k.reshape(g_, s_, k, d)
        * gate.astype(ye.dtype)[..., None], axis=2)
    return out.astype(x.dtype)


def moe_layer(
    params, x: jax.Array, cfg: MoEConfig, *, shard=None,
) -> Tuple[jax.Array, jax.Array]:
    """x: (G, S, d) -> (out (G, S, d), aux_loss scalar).

    With a mesh (shard = AxisRules.shard), dispatch/FFN/combine run INSIDE
    a shard_map over the "model" axis (true expert parallelism): every
    scatter/gather is local to a shard's experts and the only collective
    is one psum of the combined output.  Letting GSPMD partition the
    gather instead all-gathers the f32 (G, S*k, d) combine cotangent
    (7 GiB/device at arctic scale — see EXPERIMENTS §Perf).
    """
    g_, s_, d = x.shape
    e_, k = cfg.num_experts, cfg.top_k
    cap = capacity(cfg, s_)

    router_logits = jnp.einsum(
        "gsd,de->gse", x.astype(jnp.float32), params["router"])
    slot, gate, aux = route(router_logits, cfg, cap)
    aux = aux * cfg.aux_weight

    rules = getattr(shard, "__self__", None) if shard is not None else None
    mesh = getattr(rules, "mesh", None)
    if mesh is None or "model" not in mesh.axis_names \
            or e_ % mesh.shape["model"]:
        out = _dispatch_ffn_combine(params, x, slot, gate, cfg, cap, e_, 0)
        return out, aux

    from jax.sharding import PartitionSpec as P
    m = mesh.shape["model"]
    e_local = e_ // m
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    lead = batch_axes if g_ % bsz == 0 else None
    row2 = P(lead, None)
    row3 = P(lead, None, None)

    w_names = ("wi", "wg", "wo") if "wg" in params else ("wi", "wo")

    def local(w_list, x_l, slot_l, gate_l):
        rank = jax.lax.axis_index("model")
        p_local = dict(zip(w_names, w_list))
        y = _dispatch_ffn_combine(p_local, x_l, slot_l, gate_l, cfg, cap,
                                  e_local, rank * e_local)
        return jax.lax.psum(y, "model")

    w_spec = P("model", None, None)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=([w_spec] * len(w_names), row3, row2, row3),
        out_specs=row3, check_vma=False,
    )([params[n] for n in w_names], x, slot, gate)
    return out, aux
