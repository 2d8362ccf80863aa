"""Decoder-only transformer LM family (dense + MoE + frontend-stub inputs).

Covers: arctic-480b (dense-FFN residual + 128e MoE), qwen3-moe,
qwen1.5-32b, qwen3-0.6b, mistral-large-123b, qwen2-7b, and internvl2-1b
(ViT frontend stubbed: precomputed patch embeddings are concatenated ahead
of the token embeddings).

Layers are scanned (stacked params, `lax.scan`) with optional per-block
remat — compile time and HLO size stay O(1) in depth, which is what makes
the 88/94-layer dry-runs tractable.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    d_model: int
    n_layers: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # default d_model // num_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    logit_softcap: Optional[float] = None
    # MoE (num_experts == 0 -> dense)
    num_experts: int = 0
    top_k: int = 2
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    dense_ff_residual: bool = False         # arctic: dense FFN || MoE
    # frontend stub: number of precomputed embedding positions prepended
    frontend_len: int = 0
    # execution
    scan_layers: bool = True
    remat: bool = True
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    chunk_q: int = 512
    chunk_k: int = 1024
    paged_impl: str = "jax"    # paged-KV decode path (serving only)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def attn_config(self) -> A.AttnConfig:
        return A.AttnConfig(
            d_model=self.d_model, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.resolved_head_dim,
            qkv_bias=self.qkv_bias, qk_norm=self.qk_norm,
            rope_theta=self.rope_theta, chunk_q=self.chunk_q,
            chunk_k=self.chunk_k, n_layers_scale=self.n_layers,
            paged_impl=self.paged_impl)

    def moe_config(self) -> M.MoEConfig:
        return M.MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff_expert or self.d_ff,
            num_experts=self.num_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            aux_weight=self.aux_weight, n_layers_scale=self.n_layers)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_block(key, cfg: TransformerConfig):
    ks = jax.random.split(key, 4)
    dt = _pdt(cfg)
    p = {
        "ln_attn": L.init_rmsnorm(cfg.d_model, dt),
        "attn": A.init_attention(ks[0], cfg.attn_config(), dt),
        "ln_mlp": L.init_rmsnorm(cfg.d_model, dt),
    }
    if cfg.is_moe:
        p["moe"] = M.init_moe(ks[1], cfg.moe_config(), dt)
        if cfg.dense_ff_residual:
            p["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff,
                                  n_layers_scale=cfg.n_layers, dtype=dt)
    else:
        p["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff,
                              bias=False, n_layers_scale=cfg.n_layers,
                              dtype=dt)
    return p


def init_params(key, cfg: TransformerConfig) -> Dict[str, Any]:
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    dt = _pdt(cfg)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    if cfg.scan_layers:
        blocks = jax.vmap(lambda k: init_block(k, cfg))(block_keys)
    else:
        blocks = [init_block(k, cfg) for k in block_keys]
    return {
        "embed": {"table": L.embed_init(k_embed, (cfg.vocab_size,
                                                  cfg.d_model), dt)},
        "blocks": blocks,
        "ln_f": L.init_rmsnorm(cfg.d_model, dt),
        "lm_head": L.dense_init(k_head, (cfg.vocab_size, cfg.d_model),
                                dtype=dt),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def apply_block(p, x, cfg: TransformerConfig, *, cache=None, shard=None,
                decode=False, prefill_ext=False):
    """Pre-norm block; returns (x, aux, new_cache).  Each layer opens its
    scope of `repro.obs.SCOPES` here, at its call site."""
    acfg = cfg.attn_config()
    with jax.named_scope("norm"):
        xn = L.rmsnorm(p["ln_attn"], x, cfg.norm_eps)
    with jax.named_scope("attn"):
        h, new_cache = A.attention_layer(
            p["attn"], xn, acfg, cache=cache, shard=shard, decode=decode,
            prefill_ext=prefill_ext)
    x = x + h
    with jax.named_scope("norm"):
        xn = L.rmsnorm(p["ln_mlp"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe:
        with jax.named_scope("moe"):
            mo, aux = M.moe_layer(p["moe"], xn, cfg.moe_config(),
                                  shard=shard)
        if cfg.dense_ff_residual:
            with jax.named_scope("mlp"):
                mo = mo + L.mlp(p["mlp"], xn)
        x = x + mo
    else:
        with jax.named_scope("mlp"):
            y = L.mlp(p["mlp"], xn)
        if shard is not None:
            y = shard(y, "batch", "seq", "embed")
        x = x + y
    return x, aux, new_cache


def forward(
    params, tokens: jax.Array, cfg: TransformerConfig, *,
    frontend_embeds: Optional[jax.Array] = None,
    caches: Optional[Any] = None,
    shard=None,
    decode: bool = False,
    prefill_ext: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[Any]]:
    """tokens (B, T_txt) [+ frontend (B, T_img, d)] -> hidden (B, T, d).

    Returns (hidden, aux_loss, new_caches).  `hidden` covers the full
    sequence (frontend positions included); callers slice for the loss.
    ``decode=True`` (static) makes a cached T > 1 forward extend the
    cache per row instead of prefilling it — speculative verification,
    or (with ``prefill_ext=True``) the paged suffix-only prefill.
    """
    with jax.named_scope("embed"):
        x = L.embed_lookup(params["embed"]["table"], tokens,
                           shard=shard).astype(_cdt(cfg))
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    if shard is not None:
        x = shard(x, "batch", "seq", "embed")

    def block_fn(p, x, cache):
        if cfg.remat and cache is None:
            fn = jax.checkpoint(
                lambda p_, x_: apply_block(p_, x_, cfg, shard=shard)[:2],
                prevent_cse=False)
            x, aux = fn(p, x)
            return x, aux, None
        return apply_block(p, x, cfg, cache=cache, shard=shard,
                           decode=decode, prefill_ext=prefill_ext)

    with jax.named_scope("blocks"):
        if cfg.scan_layers:
            if caches is None:
                def scan_body(carry, p):
                    x, aux_sum = carry
                    x, aux, _ = block_fn(p, x, None)
                    return (x, aux_sum + aux), None

                (x, aux), _ = jax.lax.scan(
                    scan_body, (x, jnp.zeros((), jnp.float32)),
                    params["blocks"])
                new_caches = None
            else:
                def scan_body(carry, layer_in):
                    x, aux_sum = carry
                    p, cache = layer_in
                    x, aux, new_cache = block_fn(p, x, cache)
                    return (x, aux_sum + aux), new_cache

                (x, aux), new_caches = jax.lax.scan(
                    scan_body, (x, jnp.zeros((), jnp.float32)),
                    (params["blocks"], caches))
        else:
            aux = jnp.zeros((), jnp.float32)
            new_caches = [] if caches is not None else None
            for i, p in enumerate(params["blocks"]):
                c = caches[i] if caches is not None else None
                x, a, nc = block_fn(p, x, c)
                aux = aux + a
                if caches is not None:
                    new_caches.append(nc)

    with jax.named_scope("norm"):
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return x, aux, new_caches


def init_caches(cfg: TransformerConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16, quantize: bool = False):
    """Per-layer KV caches: stacked for the scan path, a list for the
    unscanned path (whose forward indexes ``caches[i]`` — a stacked dict
    there was a KeyError at the first cached forward)."""
    if not cfg.scan_layers:
        return [A.init_cache(batch, max_len, cfg.attn_config(), dtype,
                             quantize=quantize)
                for _ in range(cfg.n_layers)]
    one = A.init_cache(batch, max_len, cfg.attn_config(), dtype,
                       quantize=quantize)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape).copy(),
        one)
