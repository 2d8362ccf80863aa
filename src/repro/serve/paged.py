"""Paged serving engines: block-pool KV + shared-prefix reuse (DESIGN.md §8).

`PagedEngine` is the slot engine (`serve/engine.py`) with its dense
per-slot KV slabs replaced by the `serve/kvpool` block pool:

  * the device cache tree swaps every pageable slab subtree for
    ``{'kp', 'vp', 'table', 'len'}`` (pools shared across slots,
    per-slot block-table rows — `models/attention.py` recognizes the
    dict shape, so the model families are untouched);
  * the HOST side owns a `BlockPool` allocator, one block *chain* per
    slot, and the `PrefixCache` trie.  Before every device step the
    engine reserves chain capacity for the tokens about to be appended
    (+K+1 under speculation — rejected drafts are rolled back by length
    arithmetic exactly as on slabs, so the blocks they touched must be
    exclusively owned: `_make_writable` copy-on-writes any shared block
    in the append window, a no-op under the only-full-blocks-shared
    invariant but load-bearing for explicitly forked chains);
  * `prefill_into_slot` matches the prompt against the trie first: on a
    hit the matched chain is adopted with `fork` and ONLY the suffix is
    prefilled — a cache-extension forward (``decode=True``) over the
    shared prefix, which is what turns identical system prompts into
    near-zero time-to-first-token.

Families with nothing to page (griffin's ring buffers are already
O(window); xlstm state is O(1)) degrade transparently to the slab
engine: the paged tree equals the slab tree and every hook defers to
`Engine`.  `PagedSelfSpecEngine` composes the same cache plumbing with
the MTP self-speculative step — rollback stays block-table-truncation
(`shift_cache_lens` on the paged ``len`` leaves) and greedy output stays
token-identical to the slab engines.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import (cache_batch_axes, empty_serve_caches,
                                   merge_slot_caches, shift_cache_lens,
                                   take_slot_caches)
from repro.serve import kvpool
from repro.serve.engine import Engine, ServeConfig
from repro.serve.spec import SelfSpecEngine


class _PagedMixin:
    """Cache-plumbing overrides shared by the paged engine variants.

    Composes left of an `Engine` subclass whose prefill flows through
    the `_slot_prefill_view` / `_commit_slot` hooks and whose decode
    advances through `decode_step` / `decode_step_multi`."""

    def __init__(self, arch, params, sc: ServeConfig, *args, **kwargs):
        if getattr(arch.cfg, "frontend_len", 0):
            raise NotImplementedError(
                "paged serving does not support frontend-embedding "
                "prompts (cache positions include the frontend span, "
                "which the host block accounting does not model)")
        self._pc = kvpool.paged_config(sc.block_size, sc.max_len,
                                       sc.batch_size, sc.pool_blocks)
        # thread the paged decode impl choice into the family attn config
        if getattr(arch.cfg, "paged_impl", sc.paged_impl) != sc.paged_impl:
            arch = dataclasses.replace(
                arch, cfg=dataclasses.replace(arch.cfg,
                                              paged_impl=sc.paged_impl))
        super().__init__(arch, params, sc, *args, **kwargs)
        self._n_paged = kvpool.count_paged(self.caches)
        self._block_bytes = self._per_block_bytes()
        wrap = jax.jit if self._jit else (lambda f, **kw: f)
        dn = ({"donate_argnums": (0,)}
              if self._jit and jax.default_backend() != "cpu" else {})
        axes = self._axes
        self._merge = wrap(
            lambda caches, slot_caches, slot:
            merge_slot_caches(caches, slot_caches, slot, axes), **dn)

    # -- hooks into Engine ---------------------------------------------------

    def _cache_axes(self):
        return cache_batch_axes(self.arch, self.params, self.sc.max_len,
                                enc_len=self._enc_len, dtype=self._cdt,
                                quantize=self._quant, paged=self._pc)

    def _empty_caches(self):
        return empty_serve_caches(
            self.arch, self.params, self.sc.batch_size, self.sc.max_len,
            enc_len=self._enc_len, dtype=self._cdt, quantize=self._quant,
            paged=self._pc)

    def reset(self, seed: int = 0):
        bs, nb = self.sc.batch_size, self._pc.max_blocks_per_slot
        self.pool = kvpool.BlockPool(self._pc)
        self.prefix = (kvpool.PrefixCache(self.pool)
                       if self.sc.prefix_cache else None)
        self._chains: List[List[int]] = [[] for _ in range(bs)]
        self._host_len = np.zeros((bs,), np.int64)
        self._tables = np.full((bs, nb), kvpool.NULL_BLOCK, np.int32)
        self._tables_dirty = False
        self.prefill_tokens = 0
        self.prefill_token_log: List[int] = []
        self.prefix_hit_tokens = 0
        super().reset(seed)

    # -- host-side chain accounting ------------------------------------------

    def _per_block_bytes(self) -> int:
        """HBM bytes one pool block costs across every layer's pools —
        including the scale pools of a quantized cache (blocks are
        allocated as (kp, vp, kp_scale, vp_scale) units, so the scale
        bytes are part of what one allocation pins)."""
        total = 0

        def walk(sub):
            nonlocal total
            if kvpool.is_paged(sub):
                for key in ("kp", "vp", "kp_scale", "vp_scale"):
                    leaf = sub.get(key)
                    if leaf is None:
                        continue
                    total += leaf.size * leaf.dtype.itemsize
            elif isinstance(sub, dict):
                for v in sub.values():
                    walk(v)
            elif isinstance(sub, (list, tuple)):
                for v in sub:
                    walk(v)

        walk(self.caches)
        return total // self._pc.n_blocks if total else 0

    def live_cache_bytes(self) -> int:
        """Bytes of pool blocks currently allocated (paged HBM in use)."""
        return self.pool.used_blocks * self._block_bytes

    def _alloc_for(self, slot: int, n_tokens: int):
        """Grow `slot`'s chain to cover `n_tokens` cache positions."""
        need = self._pc.blocks_for(n_tokens) - len(self._chains[slot])
        if need <= 0:
            return
        if self.pool.free_blocks < need and self.prefix is not None:
            self.prefix.evict(need)
        new = self.pool.alloc(need)
        chain = self._chains[slot]
        start = len(chain)
        chain.extend(new)
        self._tables[slot, start:start + len(new)] = new
        self._tables_dirty = True

    def _make_writable(self, slot: int, n_tokens: int):
        """Copy-on-write every chain block the next `n_tokens` appends
        (starting at the slot's current length) will touch."""
        if n_tokens < 1:
            return
        chain = self._chains[slot]
        bsz = self._pc.block_size
        first = int(self._host_len[slot]) // bsz
        last = (int(self._host_len[slot]) + n_tokens - 1) // bsz
        for idx in range(first, min(last + 1, len(chain))):
            new, donor = self.pool.writable_block(chain, idx)
            if donor is not None:
                self.caches = kvpool.copy_block(self.caches, new, donor)
                self._tables[slot, idx] = new
                self._tables_dirty = True

    def _reserve(self, n_tokens: int):
        """Pre-step capacity: every live slot can append `n_tokens`."""
        cap = self._pc.slot_capacity
        for slot in range(self.sc.batch_size):
            if self._chains[slot]:
                target = min(int(self._host_len[slot]) + n_tokens, cap)
                self._alloc_for(slot, target)
                self._make_writable(slot, target - int(self._host_len[slot]))

    def _advance(self, counts):
        for slot in range(self.sc.batch_size):
            if self._chains[slot]:
                self._host_len[slot] = min(
                    self._host_len[slot] + int(counts[slot]),
                    self._pc.slot_capacity)

    def _sync_tables(self):
        if self._tables_dirty:
            self.caches = kvpool.fill_tables(self.caches, self._tables)
            self._tables_dirty = False

    # -- prefill (prefix match + suffix-only forward) ------------------------

    def _slot_prefill_view(self, slot: int, prompt, frontend_embeds,
                           match_len: Optional[int] = None):
        if not self._n_paged:
            return super()._slot_prefill_view(slot, prompt,
                                              frontend_embeds,
                                              match_len=match_len)
        prompt_np = np.asarray(prompt, np.int32).reshape(-1)
        if self._chains[slot]:
            raise RuntimeError(f"slot {slot} prefilled while occupied "
                               "(reset_slot it first)")
        scope = self._prefix_scope(frontend_embeds)
        shared: List[int] = []
        if self.prefix is not None:
            # `match_len` caps the trie match (eval scoring: only the
            # PROMPT may replay from cache; the continuation and the
            # token before it must run in the suffix forward)
            target = (prompt_np if match_len is None
                      else prompt_np[:match_len])
            with self._tracer.span("paged.prefix_match", cat="paged",
                                   slot=slot):
                shared = self.pool.fork(self.prefix.match(target,
                                                          scope=scope))
        shared_len = len(shared) * self._pc.block_size
        try:
            # a hit pads the SUFFIX so that shared + padded equals the
            # length a cold prefill of the full prompt would have used:
            # `extend_attention`'s per-row math then reduces over the
            # same key count as the cold blockwise tile, keeping prefix
            # hits bit-identical to cold prefills (DESIGN.md §8.2)
            pad_to = None
            if shared_len:
                pad_to = self._bucket_for(len(prompt_np)) - shared_len
            batch, base_slot, true_len = self._prefill_inputs(
                prompt_np[shared_len:], frontend_embeds,
                pad_cap=self.sc.max_len - shared_len, pad_to=pad_to)
            t_b = batch["tokens"].shape[1]
            chain = self._chains[slot] = list(shared)
            self._tables[slot, :] = kvpool.NULL_BLOCK
            self._tables[slot, :len(chain)] = chain
            self._tables_dirty = True
            self._host_len[slot] = shared_len
            self._alloc_for(slot, shared_len + t_b)
            self._make_writable(slot, t_b)
        except Exception:
            # atomic: a failed admit (e.g. PoolExhausted) releases every
            # reference it took so the caller can retry later
            self.pool.free(self._chains[slot] or shared)
            self._chains[slot] = []
            self._host_len[slot] = 0
            self._tables[slot, :] = kvpool.NULL_BLOCK
            self._tables_dirty = True
            raise
        self._sync_tables()
        view = take_slot_caches(self.caches, slot, self._axes)
        if shared_len:
            view = shift_cache_lens(view, -shared_len)
            view = kvpool.slice_tables(
                view, self._pc.blocks_for(shared_len + t_b))
        if self.arch.family == "encdec":
            view = dict(view)
            view["cross"] = base_slot["cross"]   # fresh encoder run
        self.prefill_tokens += t_b
        self.prefill_token_log.append(t_b)
        self.prefix_hit_tokens += shared_len
        ctx = {"ext": shared_len > 0, "prompt": prompt_np, "slot": slot,
               "scope": scope}
        return batch, view, true_len, ctx

    def _prefix_scope(self, frontend_embeds):
        """Trie namespace for non-token conditioning.  Enc-dec decoder
        KV depends on cross-attention over the ENCODER input, so chains
        keyed by decoder tokens alone would be reused across different
        encoder inputs — the scope is a digest of the frame embeddings
        (None means the zeros fallback, itself a distinct scope)."""
        if self.arch.family != "encdec":
            return None
        if frontend_embeds is None:
            return "enc:zeros"
        import hashlib
        raw = np.ascontiguousarray(np.asarray(frontend_embeds))
        return "enc:" + hashlib.blake2b(raw.tobytes(),
                                        digest_size=16).hexdigest()

    def _commit_slot(self, slot: int, slot_caches, ctx):
        if not self._n_paged:
            return super()._commit_slot(slot, slot_caches, ctx)
        # tables are host-authoritative (and the ext view's were sliced
        # to the cold-bucket width): restore full-width rows pre-merge
        slot_caches = kvpool.fill_tables(slot_caches,
                                         self._tables[slot:slot + 1])
        self.caches = self._merge(self.caches, slot_caches,
                                  jnp.int32(slot))
        prompt = ctx["prompt"]
        self._host_len[slot] = len(prompt)
        if self.prefix is not None:
            self.prefix.insert(prompt, self._chains[slot],
                               scope=ctx["scope"])

    # -- decode (pre-step reservation, post-step advance) --------------------

    def decode_step(self):
        if self._n_paged:
            self._reserve(1)
            self._sync_tables()
        toks = super().decode_step()
        if self._n_paged:
            self._advance(np.ones((self.sc.batch_size,), np.int64))
        return toks

    def decode_step_multi(self):
        k = int(getattr(self, "spec_k", 0))
        if not k or not self._n_paged:
            # the plain engine's multi-step delegates to decode_step,
            # which already reserves/advances — don't double-count
            return super().decode_step_multi()
        self._reserve(k + 1)
        self._sync_tables()
        toks, counts = super().decode_step_multi()
        self._advance(counts)
        return toks, counts

    def decode_topk_step(self, n_cand: int):
        if self._n_paged:
            self._reserve(1)
            self._sync_tables()
        out = super().decode_topk_step(n_cand)
        if self._n_paged:
            self._advance(np.ones((self.sc.batch_size,), np.int64))
        return out

    # -- beam forking (COW chain shares) -------------------------------------

    def fork_slot(self, dst: int, src: int) -> None:
        """Fork slot `src` into `dst` as a refcount bump on its whole
        block chain (`BlockPool.fork`): the beams share every block —
        prompt AND generated — until one writes, when `_make_writable`
        copy-on-writes only the block being appended to.  No cache
        bytes move at fork time."""
        if not self._n_paged:
            return super().fork_slot(dst, src)
        if self._chains[dst]:
            raise RuntimeError(f"fork into occupied slot {dst} "
                               "(reset_slot it first)")
        self._chains[dst] = self.pool.fork(self._chains[src])
        self._host_len[dst] = self._host_len[src]
        self._tables[dst, :] = self._tables[src, :]
        self._tables_dirty = True
        # per-slot device leaves (paged ``len``, any non-pooled state)
        # still copy row src -> dst through the slab path
        super().fork_slot(dst, src)
        self._sync_tables()

    # -- recycling -----------------------------------------------------------

    def reset_slot(self, slot: int):
        super().reset_slot(slot)
        if self._n_paged and self._chains[slot]:
            self.pool.free(self._chains[slot])
            self._chains[slot] = []
            self._host_len[slot] = 0
            self._tables[slot, :] = kvpool.NULL_BLOCK
            self._tables_dirty = True

    # -- reporting -----------------------------------------------------------

    def paged_stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"enabled": bool(self._n_paged)}
        if not self._n_paged:
            return out
        out.update({
            "block_size": self._pc.block_size,
            "pool_blocks": self._pc.n_blocks,
            "used_blocks": self.pool.used_blocks,
            "free_blocks": self.pool.free_blocks,
            "block_bytes": self._block_bytes,
            "live_cache_bytes": self.live_cache_bytes(),
            "prefill_tokens": self.prefill_tokens,
        })
        if self.prefix is not None:
            out["prefix"] = {
                "lookups": self.prefix.lookups,
                "hits": self.prefix.hits,
                "hit_rate": round(self.prefix.hits
                                  / max(self.prefix.lookups, 1), 4),
                "hit_blocks": self.prefix.hit_blocks,
                "hit_tokens": self.prefix_hit_tokens,
                "evicted_blocks": self.prefix.evicted_blocks,
                "resident_blocks": self.prefix.resident_blocks,
            }
        return out


class PagedEngine(_PagedMixin, Engine):
    """Slot engine on the paged KV cache (plain one-token decode)."""


class PagedSelfSpecEngine(_PagedMixin, SelfSpecEngine):
    """Self-speculative (MTP-head) engine on the paged KV cache.

    The verify forward appends up to K+1 entries per slot and rolls the
    rejected tail back by length arithmetic — on a paged tree that IS
    block-table truncation: the entries stay in the slot's (exclusively
    owned, pre-reserved) tail blocks and are overwritten by the next
    append, while `_make_writable` guarantees no shared prefix block is
    ever in the append window."""
