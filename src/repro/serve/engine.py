"""Slot-based serving engine: per-slot prefill + batched decode steps.

The engine treats each row of one live batched cache tree as an
independent *slot* (DESIGN.md §5.1):

  * `prefill_into_slot(i, prompt)` runs the model over one prompt at
    batch=1 (prompts bucketed to power-of-two lengths for the attention
    families, exact for recurrent ones), samples the first token, and
    splices the resulting cache into slot `i` of the live tree via the
    registry's per-slot insert — while the other slots keep decoding.
  * `decode_step()` advances EVERY slot one token with a single jitted
    forward + streaming top-k sample; the Pallas decode kernel
    (`kernels/sample_topk`) keeps the step logits-free.
  * `reset_slot(i)` restores a finished slot to its pristine state.

The engine is deliberately policy-free: admission order, EOS handling,
per-request bookkeeping, and token streaming live in
`serve/scheduler.py:ContinuousScheduler`.  `generate()` remains as a
fixed-batch convenience wrapper (it drives a private scheduler), used by
the CLI and as the drain-in-groups baseline in `benchmarks/bench_serve`.

Free slots still run the batched decode computation (their outputs are
discarded and their caches overwritten at the next prefill); an
all-masked attention row yields NaN hiddens, which stay confined to that
row — every per-row op is batch-diagonal.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import Arch, ENCDEC_SERVE_ENC_LEN
from repro.models.registry import (cache_batch_axes, cast_params,
                                   empty_serve_caches, forward_hidden,
                                   init_serve_caches, insert_slot_caches,
                                   reset_slot_caches, shift_cache_lens,
                                   take_slot_caches)
from repro.serve.sampler import sample_tokens


@dataclasses.dataclass
class ServeConfig:
    batch_size: int = 8            # number of serving slots
    max_len: int = 1024            # per-slot cache capacity (tokens)
    temperature: float = 0.0
    top_k: int = 40
    top_p: Optional[float] = None  # nucleus filter over the top-k logits
    sample_block_v: int = 8192     # vocab chunk of the 'jax' sampler impl
    cache_dtype: str = "bfloat16"
    quantize_cache: bool = False   # int8 KV (transformer family only)
    head_dtype: Optional[str] = None  # quantized lm_head serving dtype
    #   ("int8" | "float8_e4m3fn" | "float8_e5m2"; None/"bfloat16"/
    #   "float32" serve the full-precision head) — kernels/quant.py
    logit_softcap: Optional[float] = None   # None -> arch.cfg.logit_softcap
    sampler_impl: str = "pallas"   # 'pallas' kernel | 'jax' oracle
    bucket_prefill: bool = True    # pow2 prompt buckets (all families)
    enc_len: Optional[int] = None  # enc-dec encoder frames per request
    autotune: bool = False         # tune decode top-k block plans at init
    tune_trial_budget: int = 6
    # paged KV cache (serve/paged.PagedEngine, DESIGN.md §8)
    paged: bool = False            # block-pool KV instead of dense slabs
    block_size: int = 16           # tokens per pool block
    pool_blocks: int = 0           # total pool blocks (0: slab parity)
    paged_impl: str = "pallas"     # 'pallas' kernel | 'jax' gather oracle
    prefix_cache: bool = True      # shared-prefix block reuse (trie)


def resolve_logit_softcap(arch: Arch, sc: ServeConfig) -> Optional[float]:
    """Sampling softcap: explicit ServeConfig override, else the arch's.

    Threading the arch softcap is load-bearing: a Gemma-style model
    trained with capped logits must also SAMPLE from capped logits
    (monotonic, so greedy is safe, but temperature/top-p are not)."""
    if sc.logit_softcap is not None:
        return sc.logit_softcap
    return getattr(arch.cfg, "logit_softcap", None)


def make_sampler(arch: Arch, sc: ServeConfig):
    """Streaming-sampler closure over this arch's softcap + the serve
    sampling knobs.  `temperature` stays a call-site argument: the
    speculative engines draw drafts at the draft temperature and verify
    picks at the target temperature through the SAME closure.
    """
    valid = arch.vocab_size
    softcap = resolve_logit_softcap(arch, sc)

    def sample(h2, w, rng, temperature, w_scale=None):
        return sample_tokens(h2, w, rng, temperature=temperature,
                             top_k=sc.top_k, top_p=sc.top_p,
                             block_v=sc.sample_block_v, valid_vocab=valid,
                             logit_softcap=softcap, impl=sc.sampler_impl,
                             w_scale=w_scale)

    return sample


def prefill_last_hidden(arch: Arch, params, caches, batch, true_len,
                        shard=None, decode: bool = False):
    """The traced half of a batch=1 prefill: run the forward, shift the
    caches' ``len`` back by the bucket pad, and read the hidden state at
    the last REAL prompt position.  Returns (h_last (1, d), caches) —
    shared by the plain prefill and the MTP self-speculative prefill (the
    latter also applies the heads to `h_last`).

    `true_len` also gates the recurrent families' pad-step masking (their
    state consumes every position, so bucket pads must be exact no-ops).
    ``decode=True`` makes this a cache EXTENSION — the paged engine's
    suffix-only prefill after a prefix-cache hit, where the tokens attend
    over the already-cached shared prefix via `extend_attention` (whose
    rows are bit-identical to a cold blockwise prefill's)."""
    h, _, caches = forward_hidden(arch, params, batch, caches=caches,
                                  shard=shard, decode=decode,
                                  prefill_ext=decode, true_len=true_len)
    pad = batch["tokens"].shape[1] - true_len
    caches = shift_cache_lens(caches, pad)
    last = h.shape[1] - batch["tokens"].shape[1] + true_len - 1
    h_last = jax.lax.dynamic_index_in_dim(h, last, axis=1,
                                          keepdims=False)        # (1, d)
    return h_last, caches


def build_serve_fns(arch: Arch, sc: ServeConfig, shard=None):
    """(prefill, prefill_ext, decode_step) jit-ready functions.

    prefill(params, slot_caches, batch, true_len, rng) -> (tok (1,), caches)
        batch['tokens'] is (1, T_bucket) right-padded; `true_len` (traced)
        is the real prompt length — the hidden state is read at the last
        REAL position and the caches' ``len`` shifted back by the pad.
    prefill_ext: same signature, but the tokens EXTEND a non-empty cache
        (``decode=True`` forward) — the suffix-only prefill of a paged
        prefix-cache hit.  Compiled lazily; slab engines never call it.
    decode_step(params, caches, tokens (B, 1), rng) -> (tok (B,), caches)
    """
    sampler = make_sampler(arch, sc)

    def prefill(params, caches, batch, true_len, rng):
        h_last, caches = prefill_last_hidden(arch, params, caches, batch,
                                             true_len, shard=shard)
        return sampler(h_last, params["lm_head"], rng, sc.temperature,
                       w_scale=params.get("lm_head_scale")), caches

    def prefill_ext(params, caches, batch, true_len, rng):
        h_last, caches = prefill_last_hidden(arch, params, caches, batch,
                                             true_len, shard=shard,
                                             decode=True)
        return sampler(h_last, params["lm_head"], rng, sc.temperature,
                       w_scale=params.get("lm_head_scale")), caches

    def decode_step(params, caches, tokens, rng):
        h, _, caches = forward_hidden(arch, params, {"tokens": tokens},
                                      caches=caches, shard=shard)
        return sampler(h[:, -1, :], params["lm_head"], rng, sc.temperature,
                       w_scale=params.get("lm_head_scale")), caches

    return prefill, prefill_ext, decode_step


def _bucket_len(true_len: int, max_len: int) -> int:
    """Smallest power-of-two >= true_len (floor 8, capped at max_len)."""
    b = 8
    while b < true_len:
        b *= 2
    return min(b, max_len)


class Engine:
    """Slot-level serving engine over the model registry (one batched
    cache tree; rows are independently prefilled/recycled slots)."""

    # the request modes of serve/modes.py (eval scoring, beam/best-of
    # groups, constrained masks) need the single-token decode contract;
    # the speculative engines flip this off (serve/spec.py)
    supports_modes = True

    def __init__(self, arch: Arch, params, sc: ServeConfig,
                 jit: bool = True):
        self.arch = arch
        # the weights in the compute dtype once, not at every step
        # (DESIGN.md §14)
        self.params = params = cast_params(arch, params)
        self.sc = sc
        self._jit = jit
        self._cdt = jnp.dtype(sc.cache_dtype)
        if sc.quantize_cache and arch.family != "transformer":
            # never silently fall back to bf16: the caller asked for the
            # halved-footprint cache and would get full-size slabs
            raise NotImplementedError(
                "quantize_cache is only implemented for the transformer "
                f"KV cache; arch family '{arch.family}' would silently "
                "serve full-precision state — set quantize_cache=False")
        self._quant = sc.quantize_cache
        # quantized lm_head (DESIGN.md §10.2): swap the serving params'
        # head for the 1-byte copy + per-row scales once, at init — every
        # closure below reads params["lm_head"]/["lm_head_scale"]
        from repro.kernels.quant import head_quant_dtype, quantize_weight
        self._head_dtype = head_quant_dtype(sc.head_dtype)
        if self._head_dtype is not None:
            wq, ws = quantize_weight(params["lm_head"], self._head_dtype)
            self.params = dict(params)
            self.params["lm_head"] = wq
            self.params["lm_head_scale"] = ws
        self._bucketed = sc.bucket_prefill
        # bucket pads in a griffin ring buffer must never WRAP the ring
        # (a wrapped pad write destroys an in-window real entry); prompts
        # longer than the cap prefill at their exact length
        self._bucket_cap = sc.max_len
        if arch.family == "griffin":
            self._bucket_cap = min(sc.max_len, arch.cfg.window)
        self._enc_len = sc.enc_len or ENCDEC_SERVE_ENC_LEN
        # observability (repro.obs): bound at construction — free no-ops
        # unless `obs.enable()` ran first (DESIGN.md §11)
        self._tracer = obs.get_tracer()
        _reg = obs.get_registry()
        self._m_prefills = _reg.counter("engine.prefills_total")
        self._m_prefill_tokens = _reg.counter(
            "engine.prefill_tokens_total",
            "prompt tokens prefilled (bucket pad included)")
        self._m_decode_steps = _reg.counter("engine.decode_steps_total")
        self._axes = self._cache_axes()
        axes = self._axes
        # request modes (serve/modes.py): per-slot constrained-decoding
        # masks + the lazily-built mode closures (eval scoring, top-k
        # decode for beam groups) — engines without mode traffic never
        # trace them
        self._slot_masks: Dict[int, np.ndarray] = {}
        self._modefns = None

        if sc.autotune:
            self._tune_plans()

        prefill, prefill_ext, decode = build_serve_fns(arch, sc)
        wrap = jax.jit if jit else (lambda f, **kw: f)
        # donate the batched cache operand so decode/insert/reset update it
        # in place instead of copying the full tree each tick (donation is
        # unsupported — and warns — on CPU, so only ask off-CPU); the
        # prefill's slot_caches is a long-lived shared template: never
        # donated
        dn = (lambda n: {"donate_argnums": (n,)}) \
            if jit and jax.default_backend() != "cpu" else (lambda n: {})
        self._prefill = wrap(prefill)
        self._prefill_ext = wrap(prefill_ext)
        self._decode = wrap(decode, **dn(1))
        self._insert = wrap(
            lambda caches, slot_caches, slot:
            insert_slot_caches(caches, slot_caches, slot, axes), **dn(0))
        self._reset = wrap(
            lambda caches, template, slot:
            reset_slot_caches(caches, template, slot, axes), **dn(0))
        if arch.family == "encdec":
            self._enc_init = wrap(
                lambda params, fe: init_serve_caches(
                    arch, params, 1, sc.max_len, frontend_embeds=fe,
                    dtype=self._cdt))
            self._slot_init = None
        else:
            # immutable zero/pristine tree, shared by every prefill
            self._slot_init = init_serve_caches(
                arch, params, 1, sc.max_len, dtype=self._cdt,
                quantize=self._quant)
        self.reset()

    # hooks the paged engine overrides (serve/paged.py) -----------------------

    def _cache_axes(self):
        return cache_batch_axes(self.arch, self.params, self.sc.max_len,
                                enc_len=self._enc_len, dtype=self._cdt,
                                quantize=self._quant)

    def _empty_caches(self):
        return empty_serve_caches(
            self.arch, self.params, self.sc.batch_size, self.sc.max_len,
            enc_len=self._enc_len, dtype=self._cdt, quantize=self._quant)

    # -- lifecycle ----------------------------------------------------------

    @property
    def batch_size(self) -> int:
        return self.sc.batch_size

    def reset(self, seed: int = 0):
        """Fresh batched cache container + per-slot pristine template."""
        self.caches = self._empty_caches()
        self._template = take_slot_caches(self.caches, 0, self._axes)
        self.cur = np.zeros((self.sc.batch_size,), np.int32)
        self._rng = jax.random.PRNGKey(seed)
        if getattr(self, "_slot_masks", None):
            self._slot_masks.clear()

    def _tune_plans(self):
        """Populate the tuning cache for the decode/prefill sample shapes
        BEFORE the first trace, mirroring the train-side tune-at-startup."""
        from repro.kernels.sample_topk import autotune_topk_plan
        k = 1 if self.sc.temperature == 0.0 else self.sc.top_k
        v, d = self.params["lm_head"].shape
        dtype = jnp.dtype(getattr(self.arch.cfg, "compute_dtype",
                                  "float32"))
        for n in sorted({1, self.sc.batch_size}):
            autotune_topk_plan(
                n, v, d, k, dtype,
                trial_budget=self.sc.tune_trial_budget,
                logit_softcap=resolve_logit_softcap(self.arch, self.sc),
                wdtype=self._head_dtype)

    # -- slot operations ----------------------------------------------------

    def _split(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _bucket_for(self, true_len: int, cap: Optional[int] = None) -> int:
        """Padded prefill length for a `true_len`-token segment: the pow2
        bucket when bucketing is on and the bucket fits under `cap`
        (default: the family bucket cap), else the exact length."""
        if not self._bucketed:
            return true_len
        cap = self._bucket_cap if cap is None else min(cap,
                                                       self._bucket_cap)
        t_b = _bucket_len(true_len, self.sc.max_len)
        return t_b if t_b <= cap else true_len

    def _prefill_inputs(self, prompt, frontend_embeds=None,
                        pad_cap: Optional[int] = None,
                        pad_to: Optional[int] = None):
        """(batch, slot_caches, true_len) for one batch=1 prefill —
        prompt validation, pow2 bucketing, and the per-family slot-cache
        template, shared by the plain and self-speculative prefills.
        `pad_cap` additionally bounds the padded length; `pad_to` forces
        an exact padded length (the paged engine's suffix prefill pads
        the suffix so shared + padded == the cold prefill's bucket)."""
        prompt = np.asarray(prompt, np.int32).reshape(1, -1)
        true_len = prompt.shape[1]
        if not 1 <= true_len <= self.sc.max_len:
            raise ValueError(f"prompt length {true_len} outside "
                             f"[1, {self.sc.max_len}]")
        if pad_to is not None:
            if pad_to < true_len:
                raise ValueError(f"pad_to={pad_to} < prompt {true_len}")
            t_b = pad_to
        else:
            t_b = self._bucket_for(true_len, pad_cap)
        tokens = np.zeros((1, t_b), np.int32)
        tokens[0, :true_len] = prompt[0]
        batch: Dict[str, Any] = {"tokens": jnp.asarray(tokens)}

        cfg = self.arch.cfg
        if self.arch.family == "encdec":
            if frontend_embeds is None:
                frontend_embeds = jnp.zeros(
                    (1, self._enc_len, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype))
            slot_caches = self._enc_init(self.params,
                                         jnp.asarray(frontend_embeds))
        else:
            slot_caches = self._slot_init
            if getattr(cfg, "frontend_len", 0) and frontend_embeds is not None:
                batch["frontend_embeds"] = jnp.asarray(frontend_embeds)
        return batch, slot_caches, true_len

    def _slot_prefill_view(self, slot: int, prompt, frontend_embeds,
                           match_len: Optional[int] = None):
        """(batch, slot_caches, true_len, ctx) for one slot prefill.

        `ctx` is opaque state threaded to `_commit_slot`; its ``'ext'``
        key selects the cache-extension prefill variant (always False
        for the slab engine — the paged engine flips it on prefix-cache
        hits, serve/paged.py).  `match_len` caps how much of `prompt`
        the paged prefix cache may match (eval scoring must keep the
        whole continuation — and the token before it — in the suffix
        forward); slab engines have no prefix reuse and ignore it."""
        del match_len
        batch, slot_caches, true_len = self._prefill_inputs(
            prompt, frontend_embeds)
        return batch, slot_caches, true_len, {"ext": False}

    def _commit_slot(self, slot: int, slot_caches, ctx):
        """Publish a finished prefill's slot tree into the live batch."""
        del ctx
        self.caches = self._insert(self.caches, slot_caches,
                                   jnp.int32(slot))

    def prefill_into_slot(self, slot: int, prompt, frontend_embeds=None
                          ) -> int:
        """Prefill one prompt at batch=1 into slot `slot`; returns the
        FIRST sampled token (the time-to-first-token token).

        For enc-dec families a missing `frontend_embeds` runs the
        encoder on zeros — a deliberate unconditioned-decode fallback;
        pass real frames for conditioned generation."""
        batch, slot_caches, true_len, ctx = self._slot_prefill_view(
            slot, prompt, frontend_embeds)
        t_b = batch["tokens"].shape[1]
        with self._tracer.span("engine.prefill", cat="engine", slot=slot,
                               tokens=t_b, ext=bool(ctx.get("ext"))):
            if slot in self._slot_masks:
                pf = self._mode_fns().prefill_masked(bool(ctx.get("ext")))
                tok, slot_caches = pf(
                    self.params, slot_caches, batch, jnp.int32(true_len),
                    self._split(),
                    jnp.asarray(self._mask_row(slot)[None, :]))
            else:
                fn = (self._prefill_ext if ctx.get("ext")
                      else self._prefill)
                tok, slot_caches = fn(
                    self.params, slot_caches, batch, jnp.int32(true_len),
                    self._split())
            self._commit_slot(slot, slot_caches, ctx)
            tok = int(jax.device_get(tok)[0])
        self._m_prefills.inc()
        self._m_prefill_tokens.inc(t_b)
        self.cur[slot] = tok
        return tok

    def decode_step(self) -> np.ndarray:
        """Advance every slot one token; returns (B,) sampled ids.

        Rows of free slots are dead compute — callers ignore them.
        When any slot carries a constrained-decoding mask the whole
        batch routes through the masked sampler variant (unconstrained
        rows stream an all-ones mask — token-identical to no mask)."""
        with self._tracer.span("engine.decode_step", cat="engine",
                               masked=bool(self._slot_masks)):
            if self._slot_masks:
                tok, self.caches = self._mode_fns().decode_masked()(
                    self.params, self.caches,
                    jnp.asarray(self.cur[:, None]), self._split(),
                    jnp.asarray(self._mask_matrix()))
            else:
                tok, self.caches = self._decode(
                    self.params, self.caches,
                    jnp.asarray(self.cur[:, None]), self._split())
            toks = np.asarray(jax.device_get(tok), np.int32)
        self._m_decode_steps.inc()
        self.cur = toks.copy()
        return toks

    def decode_step_multi(self):
        """Variable-emission step contract shared with `serve.spec`:
        (tokens (B, T), counts (B,)) — per slot the first ``counts``
        tokens are this step's in-order emissions.  The plain engine
        always emits exactly one token per slot; `SpecEngine` overrides
        this with the draft→verify→accept→rollback cycle."""
        toks = self.decode_step()
        return toks[:, None], np.ones_like(toks)

    def reset_slot(self, slot: int):
        """Recycle a finished slot back to its pristine empty state."""
        self.caches = self._reset(self.caches, self._template,
                                  jnp.int32(slot))
        self.cur[slot] = 0
        self._slot_masks.pop(slot, None)

    # -- request modes (serve/modes.py, DESIGN.md §12) -----------------------

    def _mode_fns(self):
        """The lazily-built mode closures (compiled on first use)."""
        if self._modefns is None:
            from repro.serve.modes import ModeFns
            self._modefns = ModeFns(self)
        return self._modefns

    def set_slot_mask(self, slot: int, allowed) -> None:
        """Constrain slot `slot` to an allowed-token set (None clears).

        `allowed` is either a (vocab_size,) BOOL mask or an integer id
        list; disallowed tokens score -inf inside the sampling kernels'
        vocab scan (`sample_topk` `allowed_mask`), so they can never be
        drawn at any temperature/top-p.  The set must be non-empty."""
        if not self.supports_modes:
            raise NotImplementedError(
                f"{type(self).__name__} does not support per-slot "
                "token masks (speculative drafting would need masked "
                "verification) — serve constrained requests on a "
                "non-speculative engine")
        if allowed is None:
            self._slot_masks.pop(slot, None)
            return
        v = self.arch.vocab_size
        a = np.asarray(allowed)
        if a.dtype == np.bool_:
            if a.shape != (v,):
                raise ValueError(f"bool mask shape {a.shape} != ({v},)")
            mask = a.astype(np.uint8)
        else:
            from repro.serve.modes import allowed_ids_mask
            mask = allowed_ids_mask(a, v)
        if not mask.any():
            raise ValueError("empty allowed-token set")
        self._slot_masks[slot] = mask

    def _mask_row(self, slot: int) -> np.ndarray:
        """Slot mask padded to the lm_head's (possibly padded) vocab
        width — pad columns stay 1, the kernels' validity clamp already
        kills them."""
        vw = self.params["lm_head"].shape[0]
        row = np.ones((vw,), np.uint8)
        row[:self.arch.vocab_size] = self._slot_masks[slot]
        return row

    def _mask_matrix(self) -> np.ndarray:
        """(B, V_head) uint8 batch mask: all-ones rows (identity) except
        the slots with an active constraint."""
        m = np.ones((self.sc.batch_size, self.params["lm_head"].shape[0]),
                    np.uint8)
        for s in self._slot_masks:
            m[s] = self._mask_row(s)
        return m

    def decode_topk_step(self, n_cand: int):
        """Advance every slot one step, returning the top-`n_cand`
        candidate scores instead of sampling: (vals (B, k) f32,
        idxs (B, k) i32, lse (B,) f32) — ``vals - lse[:, None]`` are the
        candidate log-probabilities, from ONE logits-free vocab scan
        (`pallas_topk` `return_lse`).  Does NOT update `self.cur`: the
        caller (a beam/best-of group) chooses each slot's next token."""
        with self._tracer.span("engine.decode_step", cat="engine",
                               topk=n_cand):
            (vals, idxs, lse), self.caches = \
                self._mode_fns().decode_topk(n_cand)(
                    self.params, self.caches,
                    jnp.asarray(self.cur[:, None]))
            vals = np.asarray(jax.device_get(vals), np.float32)
            idxs = np.asarray(jax.device_get(idxs), np.int32)
            lse = np.asarray(jax.device_get(lse), np.float32)
        self._m_decode_steps.inc()
        return vals, idxs, lse

    def prefill_topk_into_slot(self, slot: int, prompt, n_cand: int,
                               frontend_embeds=None):
        """Prefill one prompt into `slot`, returning the first-step
        top-`n_cand` candidates (vals (k,), idxs (k,), lse scalar)
        instead of a sampled token — the admit half of a beam/best-of
        group.  Does NOT set `self.cur[slot]`; the group does."""
        batch, slot_caches, true_len, ctx = self._slot_prefill_view(
            slot, prompt, frontend_embeds)
        t_b = batch["tokens"].shape[1]
        with self._tracer.span("engine.prefill", cat="engine", slot=slot,
                               tokens=t_b, ext=bool(ctx.get("ext")),
                               topk=n_cand):
            pf = self._mode_fns().prefill_topk(n_cand,
                                               bool(ctx.get("ext")))
            (vals, idxs, lse), slot_caches = pf(
                self.params, slot_caches, batch, jnp.int32(true_len))
            self._commit_slot(slot, slot_caches, ctx)
            vals = np.asarray(jax.device_get(vals), np.float32)[0]
            idxs = np.asarray(jax.device_get(idxs), np.int32)[0]
            lse = float(np.asarray(jax.device_get(lse))[0])
        self._m_prefills.inc()
        self._m_prefill_tokens.inc(t_b)
        return vals, idxs, lse

    def score_in_slot(self, slot: int, prompt, continuation,
                      frontend_embeds=None) -> np.ndarray:
        """Per-token ``log p(continuation | prompt)`` — (len(cont),)
        f32 — in ONE batch=1 forward over prompt+continuation through
        slot `slot` (the loglikelihood/perplexity eval primitive).

        The hidden state at each continuation position feeds
        `kernels/score_tokens` (candidate logit + lse per row, never a
        logits row).  On paged engines the prompt prefix replays through
        the prefix-cache trie (`match_len` caps the match at the prompt
        so the scored positions stay inside the suffix forward), making
        N continuations of one prompt N cheap suffix extensions.  The
        slot's cache is left holding prompt+continuation — the caller
        resets (or reuses) the slot."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cont = np.asarray(continuation, np.int32).reshape(-1)
        if cont.size == 0:
            return np.zeros((0,), np.float32)
        seq = np.concatenate([prompt, cont])
        batch, slot_caches, true_len, ctx = self._slot_prefill_view(
            slot, seq, frontend_embeds, match_len=len(prompt))
        t_b = batch["tokens"].shape[1]
        p_pad = max(8, _bucket_len(len(cont), 1 << 30))
        ids = np.full((p_pad,), -1, np.int32)
        ids[:len(cont)] = cont
        with self._tracer.span("engine.prefill", cat="engine", slot=slot,
                               tokens=t_b, ext=bool(ctx.get("ext")),
                               mode="eval"):
            fn = self._mode_fns().eval_score(p_pad,
                                             bool(ctx.get("ext")))
            logp, slot_caches = fn(
                self.params, slot_caches, batch, jnp.int32(true_len),
                jnp.int32(len(cont)), jnp.asarray(ids))
            self._commit_slot(slot, slot_caches, ctx)
            logp = np.asarray(jax.device_get(logp), np.float32)
        self._m_prefills.inc()
        self._m_prefill_tokens.inc(t_b)
        return logp[:len(cont)]

    def fork_slot(self, dst: int, src: int) -> None:
        """Duplicate slot `src`'s decode state into free slot `dst`
        (beam / best-of-n forking).  The slab engine copies the cache
        row; `PagedEngine` overrides this with a `BlockPool.fork`
        refcount bump — sibling beams share every block copy-on-write
        until they diverge (serve/paged.py)."""
        view = take_slot_caches(self.caches, jnp.int32(src), self._axes)
        self.caches = self._insert(self.caches, view, jnp.int32(dst))
        self.cur[dst] = self.cur[src]

    # -- fixed-batch convenience -------------------------------------------

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 eos_id: Optional[int] = None, seed: int = 0,
                 frontend_embeds=None) -> np.ndarray:
        """prompts: (R, T_prompt) int32.  Returns (R, max_new_tokens)
        generated ids (post-eos positions repeat eos).

        `frontend_embeds` (batch=1, shared by every request) is required
        for meaningful enc-dec output — without it each slot's encoder
        runs on zeros (see `prefill_into_slot`).

        Drives a private `ContinuousScheduler`, so finished slots ARE
        recycled from the queue mid-flight — but the call itself still
        blocks until every request finishes (use the scheduler directly
        for streaming)."""
        from repro.serve.scheduler import ContinuousScheduler

        self.reset(seed)
        sched = ContinuousScheduler(self, max_new_tokens=max_new_tokens,
                                    eos_id=eos_id)
        rids = [sched.submit(p, frontend_embeds=frontend_embeds)
                for p in np.asarray(prompts, np.int32)]
        results = sched.run()
        fill = eos_id if eos_id is not None else 0
        out = np.full((len(rids), max_new_tokens), fill, np.int32)
        for i, rid in enumerate(rids):
            toks = results[rid]
            out[i, :len(toks)] = toks
        return out
