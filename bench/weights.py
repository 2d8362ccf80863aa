"""Weights made by the benchmark from the seed, in the program's layout.

One jitted call makes every leaf on the device, in the type the
configuration keeps its parameters in (the `param_dtype` of its
overrides, else its `torch_dtype`).  The layout is the parameter
tree the program's transformer family takes (stacked over layers), so the
same tree feeds the program and, upcast to f32, the plain reference in
`bench/reference.py`.  Nothing here comes from the program: the program
only receives the tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def model_spec(cfg: dict) -> dict:
    """The sizes the weights, the reference and the counts need, read
    from a configuration file's `model` block."""
    m = cfg["model"]
    v = m["vocab_size"]
    pad = m.get("vocab_pad_multiple", 256)
    return {
        "d": m["hidden_size"], "layers": m["num_hidden_layers"],
        "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
        "head_dim": m["head_dim"], "ff": m["intermediate_size"],
        "vocab": v, "padded_vocab": -(-v // pad) * pad,
        "qkv_bias": bool(m.get("attention_bias", False)),
        "qk_norm": bool(m.get("qk_norm", False)),
        "rope_theta": float(m["rope_theta"]), "eps": float(m["rms_norm_eps"]),
        "dtype": cfg.get("overrides", {}).get(
            "param_dtype", m.get("torch_dtype", "bfloat16")),
    }


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def make_params(spec: dict, key: jax.Array, dtype=None):
    """The parameter tree for `spec`, drawn from `key` (traceable)."""
    dt = jnp.dtype(dtype or spec["dtype"])
    d, L, nq, nkv, hd, f = (spec[k] for k in
                            ("d", "layers", "heads", "kv_heads", "head_dim",
                             "ff"))
    v, vp = spec["vocab"], spec["padded_vocab"]
    k = iter(jax.random.split(key, 16))
    out_std = 1.0 / math.sqrt(2.0 * L)
    attn = {
        "wq": _normal(next(k), (L, d, nq, hd), 1 / math.sqrt(d)),
        "wk": _normal(next(k), (L, d, nkv, hd), 1 / math.sqrt(d)),
        "wv": _normal(next(k), (L, d, nkv, hd), 1 / math.sqrt(d)),
        "wo": _normal(next(k), (L, nq, hd, d), out_std / math.sqrt(nq * hd)),
    }
    if spec["qkv_bias"]:
        attn["bq"] = _normal(next(k), (L, nq, hd), 0.02)
        attn["bk"] = _normal(next(k), (L, nkv, hd), 0.02)
        attn["bv"] = _normal(next(k), (L, nkv, hd), 0.02)
    if spec["qk_norm"]:
        attn["q_norm"] = 1.0 + _normal(next(k), (L, hd), 0.02)
        attn["k_norm"] = 1.0 + _normal(next(k), (L, hd), 0.02)
    head = _normal(next(k), (v, d), 1 / math.sqrt(d))
    params = {
        "embed": {"table": _normal(next(k), (v, d), 0.02)},
        "blocks": {
            "ln_attn": {"scale": 1.0 + _normal(next(k), (L, d), 0.02)},
            "attn": attn,
            "ln_mlp": {"scale": 1.0 + _normal(next(k), (L, d), 0.02)},
            "mlp": {"wi": _normal(next(k), (L, d, f), 1 / math.sqrt(d)),
                    "wg": _normal(next(k), (L, d, f), 1 / math.sqrt(d)),
                    "wo": _normal(next(k), (L, f, d),
                                  out_std / math.sqrt(f))},
        },
        "ln_f": {"scale": 1.0 + _normal(next(k), (d,), 0.02)},
        "lm_head": jnp.pad(head, ((0, vp - v), (0, 0))),
    }
    return jax.tree.map(lambda x: x.astype(dt), params)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**63 (two 32-bit words)."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.array([s >> 32, s & 0xFFFFFFFF], jnp.uint32))
