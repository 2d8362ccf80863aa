"""Shared neural-net layers (pure-JAX, pytree params, no framework).

Conventions:
  * params are nested dicts of jnp arrays; every layer has
    `init_<layer>(key, ...) -> params` and `<layer>(params, x, ...)`.
  * computation dtype follows the input; normalization statistics and
    softmax-like reductions run in f32.
  * weight layout is chosen so the natural contraction dim is last/first in
    a way that keeps TPU-friendly (128-lane) minor dimensions.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale: float = 1.0, dtype=jnp.float32):
    """Truncated-normal fan-in init (stddev = scale / sqrt(fan_in))."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = scale / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def embed_lookup(table, tokens, shard=None):
    """Embedding lookup; TP-sharded tables gather locally via shard_map.

    The table is sharded (None, "model") on its d_model dim (see
    sharding.rules).  GSPMD's gather partitioner mishandles that layout
    (invalid dynamic-slice after spmd-partitioning on XLA CPU), and a
    vocab-sharded table makes the *backward* scatter-add all-gather the
    full f32 activation rows.  A shard_map local gather has zero
    communication forward and a local scatter-add + data-axis psum
    backward — strictly the best layout.  `shard` is the AxisRules.shard
    bound method; its __self__ carries the mesh.
    """
    rules = getattr(shard, "__self__", None) if shard is not None else None
    mesh = getattr(rules, "mesh", None)
    if mesh is None or "model" not in mesh.axis_names \
            or table.shape[1] % mesh.shape["model"]:
        return table[tokens]
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    lead = batch_axes if (batch_axes and tokens.shape[0] % bsz == 0) \
        else None
    tok_spec = P(lead, *([None] * (tokens.ndim - 1)))
    out_spec = P(lead, *([None] * (tokens.ndim - 1)), "model")

    def local(tab_l, tok_l):
        return tab_l[tok_l]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, "model"), tok_spec),
        out_specs=out_spec, check_vma=False)(table, tokens)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def init_rmsnorm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(
        jnp.float32)
    return y.astype(x.dtype)


def head_rmsnorm(scale, x, eps: float = 1e-6):
    """qk-norm: RMS over the head dim of (..., heads, head_dim)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_angles(positions, head_dim: int, theta: float = 10000.0):
    """(..., T) int positions -> cos/sin of shape (..., T, head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., T, heads, head_dim); cos/sin: (..., T, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(jnp.float32)
    s = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d_model, d_ff, *, gated: bool = True, bias: bool = False,
             n_layers_scale: int = 1, dtype=jnp.float32):
    """SwiGLU (gated) or GeLU MLP params."""
    ks = jax.random.split(key, 3)
    out_scale = 1.0 / np.sqrt(2.0 * max(n_layers_scale, 1))
    p = {
        "wi": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "wo": dense_init(ks[1], (d_ff, d_model), scale=out_scale,
                         dtype=dtype),
    }
    if gated:
        p["wg"] = dense_init(ks[2], (d_model, d_ff), dtype=dtype)
    if bias:
        p["bi"] = jnp.zeros((d_ff,), dtype)
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


def mlp(params, x):
    up = jnp.einsum("...d,df->...f", x, params["wi"])
    if "bi" in params:
        up = up + params["bi"]
    if "wg" in params:
        gate = jnp.einsum("...d,df->...f", x, params["wg"])
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        act = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("...f,fd->...d", act, params["wo"])
    if "bo" in params:
        out = out + params["bo"]
    return out


# ---------------------------------------------------------------------------
# causal depthwise conv (xLSTM / Griffin temporal conv)
# ---------------------------------------------------------------------------


def init_causal_conv(key, dim, width: int = 4, dtype=jnp.float32):
    return {
        "w": dense_init(key, (width, dim), dtype=dtype),
        "b": jnp.zeros((dim,), dtype),
    }


def causal_conv(params, x, state: Optional[jax.Array] = None):
    """Depthwise causal 1D conv.

    x: (B, T, D).  If `state` is given it is the last (width-1) inputs from
    the previous segment (decode path); returns (y, new_state).
    """
    w = params["w"]
    width = w.shape[0]
    if state is None:
        state = jnp.zeros(x.shape[:1] + (width - 1,) + x.shape[2:], x.dtype)
    xx = jnp.concatenate([state, x], axis=1)            # (B, T+w-1, D)
    y = sum(xx[:, i:i + x.shape[1]] * w[i] for i in range(width))
    y = y + params["b"]
    new_state = xx[:, -(width - 1):] if width > 1 else state
    return y.astype(x.dtype), new_state


def conv_state_at(prev_state, x, true_len):
    """Conv carry as if only the first `true_len` steps of x were consumed.

    The bucketed-prefill corrector for recurrent families (DESIGN.md
    §5.1): `causal_conv` over a tail-padded segment returns the last
    (width-1) inputs INCLUDING the pads; the true carry is the (width-1)
    inputs ending at position ``true_len - 1`` of ``[prev_state; x]``
    (which falls back into `prev_state` when ``true_len < width - 1``).
    ``true_len`` may be traced.
    """
    xx = jnp.concatenate([prev_state, x.astype(prev_state.dtype)], axis=1)
    w1 = prev_state.shape[1]
    return jax.lax.dynamic_slice_in_dim(xx, true_len, w1, axis=1)
