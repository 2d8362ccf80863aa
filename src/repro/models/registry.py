"""Unified model API over the four families + the --arch registry.

Every family exposes:  init_params / forward(hidden) / serve caches.
The registry pads the lm_head to `arch.padded_vocab` rows (so the vocab
axis divides the mesh and the fused-CE BlockSpecs evenly); the pad columns
are masked to -inf inside every loss implementation via
`arch.loss_config().valid_vocab`.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import Arch

# Families whose trunks take the registry-level MTP heads (DESIGN.md §7).
# Heads are position-wise post-trunk blocks, so any decoder-only LM trunk
# qualifies; enc-dec is excluded (its serve path is encoder-conditioned).
MTP_FAMILIES = ("transformer", "griffin", "xlstm")

_CONFIG_MODULES = {
    "arctic-480b": "repro.configs.arctic_480b",
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b",
    "qwen1.5-32b": "repro.configs.qwen15_32b",
    "qwen3-0.6b": "repro.configs.qwen3_0_6b",
    "mistral-large-123b": "repro.configs.mistral_large_123b",
    "qwen2-7b": "repro.configs.qwen2_7b",
    "xlstm-125m": "repro.configs.xlstm_125m",
    "internvl2-1b": "repro.configs.internvl2_1b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "recurrentgemma-9b": "repro.configs.recurrentgemma_9b",
    "paper-lm": "repro.configs.paper_lm",
}

ARCH_IDS = tuple(k for k in _CONFIG_MODULES if k != "paper-lm")


def get_arch(arch_id: str, *, reduced: bool = False, **overrides) -> Arch:
    if arch_id not in _CONFIG_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: "
                       f"{sorted(_CONFIG_MODULES)}")
    mod = importlib.import_module(_CONFIG_MODULES[arch_id])
    return mod.reduced() if reduced else mod.get_config(**overrides)


def _family_mod(arch: Arch):
    return importlib.import_module(f"repro.models.{arch.family}")


def supports_mtp(arch: Arch) -> bool:
    """True when this arch can carry multi-token prediction heads AND its
    config block asks for them (`arch.mtp.n_heads > 0`)."""
    return arch.family in MTP_FAMILIES and arch.mtp.n_heads > 0


def init_params(arch: Arch, rng: jax.Array):
    mod = _family_mod(arch)
    params = mod.init_params(rng, arch.cfg)
    pad = arch.padded_vocab - arch.vocab_size
    if pad:
        params["lm_head"] = jnp.pad(params["lm_head"], ((0, pad), (0, 0)))
    if arch.mtp.n_heads:
        if arch.family not in MTP_FAMILIES:
            raise ValueError(
                f"family {arch.family!r} does not support MTP heads "
                f"(supported: {MTP_FAMILIES})")
        from repro.models.mtp import init_heads
        params["mtp"] = init_heads(
            jax.random.fold_in(rng, 0x4d54), arch.cfg.d_model, arch.mtp,
            dtype=jnp.dtype(getattr(arch.cfg, "param_dtype", "float32")))
    return params


def apply_mtp_heads(arch: Arch, params, h: jax.Array) -> jax.Array:
    """Per-head hidden states (..., n, d) from trunk hiddens (..., d)."""
    if "mtp" not in params:
        raise ValueError(
            "params carry no 'mtp' head subtree — init them via "
            "init_params on an arch with mtp.n_heads > 0")
    from repro.models.mtp import apply_heads
    return apply_heads(params["mtp"], h,
                       eps=getattr(arch.cfg, "norm_eps", 1e-6))


def cast_params(arch: Arch, params):
    """The master weights as the forward computes with them (DESIGN.md
    §14): every floating leaf in the config's `compute_dtype`, the
    gradient flowing back through the cast into the stored dtype.
    Callers open the `cast` scope of `repro.obs.SCOPES` around it.  The
    embedding table is left as stored: the forward casts the rows it
    looks up, so the table's gradient accumulates in the stored dtype
    too.  With `param_dtype` equal to `compute_dtype` this returns
    `params` itself, and a leaf already in `compute_dtype` passes
    unchanged.  Counts, as it is traced, the leaves it casts
    (``precision.cast_leaves``) and their stored bytes
    (``precision.cast_bytes``)."""
    cdt = jnp.dtype(getattr(arch.cfg, "compute_dtype", "float32"))
    if jnp.dtype(getattr(arch.cfg, "param_dtype", "float32")) == cdt:
        return params
    cast = []

    def one(path, x):
        if (path[0].key == "embed" or x.dtype == cdt
                or not jnp.issubdtype(x.dtype, jnp.floating)):
            return x
        cast.append(x.size * x.dtype.itemsize)
        return x.astype(cdt)

    out = jax.tree_util.tree_map_with_path(one, params)
    leaves, nbytes = obs.PRECISION_COUNTERS
    reg = obs.get_registry()
    reg.counter(leaves).inc(len(cast))
    reg.counter(nbytes).inc(sum(cast))
    return out


def forward_hidden(
    arch: Arch, params, batch: Dict[str, Any], *,
    caches=None, shard=None, decode: bool = False,
    prefill_ext: bool = False,
    return_heads: bool = False, true_len=None,
):
    """(hidden aligned with batch['targets'], aux_loss, new_caches).

    ``decode=True`` (static) marks a cached T > 1 forward as a cache
    EXTENSION (per-row append + full-cache causal attention — the
    speculative-verification path and the paged engine's suffix-only
    prefill) rather than a fresh prefill.  Recurrent families are
    sequential either way and ignore it.

    ``true_len`` (traced scalar, serving only): positions at or beyond
    it are bucket pads.  Attention families need no masking (pad cache
    entries are position-addressed: invisible after the `len` shift,
    overwritten by later appends), but recurrent state consumes every
    step — griffin/xlstm forwards gate the pad steps into exact no-ops.

    ``return_heads=True`` (static; needs `arch.mtp.n_heads > 0`) returns
    the 4-tuple (hidden, head_hidden (B, T, n, d), aux_loss, new_caches):
    the trunk hiddens plus the per-horizon MTP head hiddens — the
    training path applies the fused CE to every horizon from this one
    forward (DESIGN.md §7.1).
    """
    mod = _family_mod(arch)
    with jax.named_scope("cast"):
        params = cast_params(arch, params)
    kwargs = dict(shard=shard, decode=decode)
    fe = batch.get("frontend_embeds")
    if arch.family == "transformer":
        h, aux, c = mod.forward(params, batch["tokens"], arch.cfg,
                                frontend_embeds=fe, caches=caches,
                                prefill_ext=prefill_ext, **kwargs)
    elif arch.family == "encdec":
        h, aux, c = mod.forward(params, batch["tokens"], arch.cfg,
                                frontend_embeds=fe, caches=caches,
                                prefill_ext=prefill_ext, **kwargs)
    else:  # xlstm / griffin
        h, aux, c = mod.forward(params, batch["tokens"], arch.cfg,
                                states=caches, true_len=true_len, **kwargs)
    if return_heads:
        return h, apply_mtp_heads(arch, params, h), aux, c
    return h, aux, c


def init_serve_caches(arch: Arch, params, batch_size: int, max_len: int,
                      *, frontend_embeds=None, dtype=jnp.bfloat16,
                      shard=None, quantize: bool = False):
    mod = _family_mod(arch)
    if arch.family == "transformer":
        return mod.init_caches(arch.cfg, batch_size, max_len, dtype,
                               quantize=quantize)
    if arch.family == "encdec":
        return mod.init_caches(params, arch.cfg, frontend_embeds, max_len,
                               dtype, shard=shard)
    if arch.family == "xlstm":
        return mod.init_states(arch.cfg, batch_size)
    return mod.init_states(arch.cfg, batch_size, dtype)   # griffin


# ---------------------------------------------------------------------------
# per-slot cache surgery (continuous batching, DESIGN.md §5.2)
#
# The slot engine keeps ONE batched cache tree and treats each batch row as
# an independent serving slot: a new request is prefilled at batch=1 and its
# cache inserted into the live tree; a finished slot is reset in place.  The
# helpers below are family-agnostic — the batch axis of every leaf is
# discovered structurally (eval_shape at two batch sizes), so transformer KV
# stacks, Griffin/xLSTM recurrent state, quantized caches, and enc-dec
# cross-KV all work through the same three tree operations.
# ---------------------------------------------------------------------------


def _slot_cache_specs(arch: Arch, params, batch_size: int, max_len: int,
                      enc_len: Optional[int], dtype, quantize: bool,
                      paged=None):
    """ShapeDtypeStruct tree of the serve cache at `batch_size` — the one
    abstract cache builder behind `empty_serve_caches`/`cache_batch_axes`
    (so the discovered batch axes can never diverge from the real tree).

    For enc-dec the encoder input is a spec, so no encoder runs.
    `paged` (a `serve.kvpool.PagedConfig`) rewrites pageable slab KV
    subtrees into their block-pool form (DESIGN.md §8)."""
    from repro.configs.base import ENCDEC_SERVE_ENC_LEN

    if arch.family == "encdec":
        fe = jax.ShapeDtypeStruct(
            (batch_size, enc_len or ENCDEC_SERVE_ENC_LEN,
             arch.cfg.d_model), jnp.dtype(arch.cfg.compute_dtype))
        specs = jax.eval_shape(
            lambda p, f: init_serve_caches(arch, p, batch_size, max_len,
                                           frontend_embeds=f, dtype=dtype),
            params, fe)
    else:
        specs = jax.eval_shape(
            lambda p: init_serve_caches(arch, p, batch_size, max_len,
                                        dtype=dtype,
                                        quantize=quantize
                                        and arch.family == "transformer"),
            params)
    if paged is not None:
        from repro.serve.kvpool import paged_tree
        specs = jax.eval_shape(lambda t: paged_tree(t, paged), specs)
    return specs


def empty_serve_caches(arch: Arch, params, batch_size: int, max_len: int,
                       *, enc_len: Optional[int] = None,
                       dtype=jnp.bfloat16, quantize: bool = False,
                       paged=None):
    """Batched cache container whose slots await per-slot prefill inserts.

    For every family but enc-dec this IS `init_serve_caches` (cheap, and
    it preserves non-zero init like the ring-buffer ``pos = -1``).  For
    enc-dec, `init_serve_caches` would run the encoder — pointless for
    empty slots — so the container is zeros materialized from its specs;
    per-slot prefill runs the encoder and overwrites the slot slice.

    `paged` (a `serve.kvpool.PagedConfig`): pageable slab KV subtrees
    become block pools + per-slot tables (zero tables = every slot at
    the reserved null block).  For families that actually page
    (transformer / enc-dec — whose empty containers are all-zeros) the
    tree is materialized from SPECS: going through a concrete slab
    donor would transiently allocate the full dense-slab HBM the pool
    exists to replace.  Families with nothing pageable keep the plain
    container (preserving non-zero init like the ring ``pos = -1``).
    """
    if paged is not None:
        if arch.family in ("transformer", "encdec"):
            specs = _slot_cache_specs(arch, params, batch_size, max_len,
                                      enc_len, dtype, quantize,
                                      paged=paged)
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                specs)
        return empty_serve_caches(arch, params, batch_size, max_len,
                                  enc_len=enc_len, dtype=dtype,
                                  quantize=quantize)
    if arch.family != "encdec":
        return init_serve_caches(arch, params, batch_size, max_len,
                                 dtype=dtype,
                                 quantize=quantize
                                 and arch.family == "transformer")
    specs = _slot_cache_specs(arch, params, batch_size, max_len, enc_len,
                              dtype, quantize)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)


def cache_batch_axes(arch: Arch, params, max_len: int,
                     *, enc_len: Optional[int] = None,
                     dtype=jnp.bfloat16, quantize: bool = False,
                     paged=None):
    """Per-leaf batch-axis pytree for the serve cache (-1: no batch axis).

    Found structurally: build the cache specs at batch 1 and 2 and take
    the (unique) axis whose size differs.  Returns a pytree of ints with
    the cache's exact structure, usable as a `jax.tree.map` companion.
    Paged pool leaves (``kp``/``vp``) are batch-size invariant — they are
    SHARED across slots — so the discovery marks them -1 and the per-slot
    take/insert surgery leaves them alone by construction.
    """
    def build(b):
        return _slot_cache_specs(arch, params, b, max_len, enc_len,
                                 dtype, quantize, paged=paged)

    def axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        return diffs[0] if diffs else -1

    return jax.tree.map(axis, build(1), build(2))


def take_slot_caches(caches, slot, axes):
    """Slice one slot (size-1 batch dim kept) out of a batched cache."""
    return jax.tree.map(
        lambda leaf, ax: leaf if ax < 0 else
        jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax),
        caches, axes)


def insert_slot_caches(caches, slot_caches, slot, axes):
    """Write a batch=1 cache tree into slot `slot` of a batched cache.

    `slot` may be traced (one compilation serves every slot).  Leaves
    without a batch axis are left untouched.
    """
    return jax.tree.map(
        lambda big, small, ax: big if ax < 0 else
        jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=ax),
        caches, slot_caches, axes)


def merge_slot_caches(caches, slot_caches, slot, axes):
    """`insert_slot_caches` that also ADOPTS unbatched leaves from the
    slot tree.

    For slab trees every leaf has a batch axis and this is exactly
    `insert_slot_caches`.  For paged trees (DESIGN.md §8) the block
    pools carry no batch axis (``ax < 0``): a batch=1 prefill writes the
    slot's tokens straight into the SHARED pools, so the returned slot
    tree's pool leaves are the authoritative ones and must replace the
    batched tree's — `insert_slot_caches` would silently discard them.
    """
    return jax.tree.map(
        lambda big, small, ax: small.astype(big.dtype) if ax < 0 else
        jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=ax),
        caches, slot_caches, axes)


def reset_slot_caches(caches, template, slot, axes):
    """Restore slot `slot` to its pristine (empty) state.

    `template` is a batch=1 slice of a freshly initialized cache (NOT
    plain zeros: ring-buffer position buffers initialize to -1)."""
    return insert_slot_caches(caches, template, slot, axes)


def shift_cache_lens(caches, delta):
    """Subtract `delta` from every ``"len"`` leaf of a cache tree.

    Used by bucketed prefill (transformer / enc-dec): prompts are padded
    to a bucket length before the prefill forward, which advances the
    attention caches' ``len`` by the padded length; shifting by the pad
    restores the true prompt length so decode resumes at the right
    position (pad rows beyond it are dead and get overwritten).

    `delta` may be traced, and may be a PER-SLOT ``(B,)`` array — the
    speculative-decoding rollback (DESIGN.md §6.4): each slot retracts
    its own count of rejected drafted positions (``len`` leaves are
    ``(B,)`` or layer-stacked ``(L, B)``, both broadcast).  Entries past
    the shifted length become invisible to causally masked decode reads
    and are overwritten by the next per-row append, for plain, quantized
    and ring-buffer caches alike.  Recurrent state (no ``len`` leaves)
    passes through — roll it back with `select_step_caches` instead.
    """
    if isinstance(caches, dict):
        return {key: (val - delta if key == "len"
                      else shift_cache_lens(val, delta))
                for key, val in caches.items()}
    if isinstance(caches, (list, tuple)):
        return type(caches)(shift_cache_lens(v, delta) for v in caches)
    return caches


def _has_len_leaf(caches) -> bool:
    if isinstance(caches, dict):
        return "len" in caches or any(_has_len_leaf(v)
                                      for v in caches.values())
    if isinstance(caches, (list, tuple)):
        return any(_has_len_leaf(v) for v in caches)
    return False


def spec_cache_strategy(arch: Arch) -> str:
    """How this family's serve caches roll back rejected drafted tokens.

    ``'len'``   — attention KV caches (transformer / enc-dec): entries
                  are position-addressed, so rollback is per-slot length
                  arithmetic (`rollback_slot_caches`) and verification
                  is ONE cached multi-token forward (``decode=True``).
    ``'scan'``  — recurrent state (griffin / xlstm): state is a running
                  reduction that cannot be partially undone, so the
                  verifier steps token-by-token, stacks the per-step
                  state snapshots, and rollback SELECTS each slot's
                  surviving snapshot (`select_step_caches`).
    """
    return "len" if arch.family in ("transformer", "encdec") else "scan"


def rollback_slot_caches(caches, n_reject):
    """Retract `n_reject` (scalar or per-slot ``(B,)``) entries from the
    tail of every position-addressed cache in the tree.

    This is the speculative-decoding rollback for ``'len'``-strategy
    families: the verify forward appended K+1 entries per slot, the
    acceptance rule kept ``a+1 <= K+1`` of them, and the rest become
    dead tail entries (masked now, overwritten by the next append).

    Raises for trees with no ``len`` leaves (recurrent state) — length
    arithmetic would silently corrupt them; use `select_step_caches`.
    """
    if not _has_len_leaf(caches):
        raise ValueError(
            "rollback_slot_caches needs position-addressed caches with "
            "'len' leaves; recurrent state rolls back via "
            "select_step_caches (spec_cache_strategy == 'scan')")
    return shift_cache_lens(caches, n_reject)


def select_step_caches(stacked, step, axes):
    """Pick each slot's cache tree out of a stacked per-step snapshot.

    `stacked`: the serve-cache tree with an extra LEADING step axis —
    ``leaf[s]`` is the cache state after consuming ``s`` tokens of the
    speculative step (s = 0..K+1).  `step` (B,) selects, per slot, the
    snapshot that survives acceptance (``accepted + 1`` consumed
    tokens); `axes` is the `cache_batch_axes` tree of the UNSTACKED
    cache.  Leaves without a batch axis take the last step.
    """
    b = step.shape[0]
    rows = jnp.arange(b)

    def pick(leaf, ax):
        if ax < 0:
            return leaf[-1]
        moved = jnp.moveaxis(leaf, ax + 1, 1)        # (S+1, B, ...)
        return jnp.moveaxis(moved[step, rows], 0, ax)

    return jax.tree.map(pick, stacked, axes)


def rollback_snapshot_caches(snaps, step, n_reject, axes):
    """Per-slot rollback from per-step snapshots (the 'scan' strategy).

    `snaps`: S+1 cache trees, ``snaps[s]`` the state after consuming
    ``s`` tokens of the speculative step.  Linear append-only subtrees
    — dicts with a ``len`` leaf but no ``pos`` — roll back by length
    arithmetic on the LAST snapshot alone (their big KV leaves are
    never stacked S+1 times); everything else, recurrent leaves AND
    ring-buffer caches, gathers each slot's surviving snapshot via
    `select_step_caches`.

    Ring buffers (``pos`` present) MUST take the snapshot path even
    though they carry ``len``: a ring append at slot ``(len+i) % W``
    OVERWRITES the entry that was ``W`` positions back — still inside
    the attention window — so once the sequence wraps, rejected
    appends destroy history that no length shift can restore.
    """
    def walk(subs, ax):
        first = subs[0]
        if isinstance(first, dict) and "len" in first \
                and "pos" not in first:
            return shift_cache_lens(subs[-1], n_reject)
        if isinstance(first, dict):
            return {k: walk([s[k] for s in subs], ax[k]) for k in first}
        if isinstance(first, (list, tuple)):
            return type(first)(walk([s[i] for s in subs], ax[i])
                               for i in range(len(first)))
        return select_step_caches(jnp.stack(subs), step, ax)

    return walk(list(snaps), axes)


def serve_cache_specs(arch: Arch, batch_size: int, max_len: int,
                      dtype=jnp.bfloat16, quantize: bool = False):
    """ShapeDtypeStruct tree of the decode-step cache (dry-run input)."""
    from repro.configs.base import ENCDEC_SERVE_ENC_LEN

    def build():
        if arch.family == "encdec":
            d = arch.cfg.d_model
            fe = jnp.zeros((batch_size, ENCDEC_SERVE_ENC_LEN, d),
                           jnp.dtype(arch.cfg.compute_dtype))
            params = init_params(arch, jax.random.PRNGKey(0))
            return init_serve_caches(arch, params, batch_size, max_len,
                                     frontend_embeds=fe, dtype=dtype)
        return init_serve_caches(arch, None, batch_size, max_len,
                                 dtype=dtype,
                                 quantize=quantize and
                                 arch.family == "transformer")

    return jax.eval_shape(build)


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_param_count(arch: Arch, params) -> int:
    """Active params per token (MoE: top_k of num_experts experts)."""
    total = param_count(params)
    cfg = arch.cfg
    if getattr(cfg, "num_experts", 0):
        moe_total = 0
        blocks = params["blocks"]
        for name in ("wi", "wg", "wo"):
            leaf = blocks.get("moe", {}).get(name) if isinstance(
                blocks, dict) else None
            if leaf is not None:
                moe_total += leaf.size
        active_frac = cfg.top_k / cfg.num_experts
        return int(total - moe_total * (1.0 - active_frac))
    return total
