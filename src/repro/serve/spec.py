"""Speculative decoding: draft-propose K tokens, verify them logits-free.

One engine step serves every slot up to K+1 tokens (DESIGN.md §6):

  1. **propose** — a small draft model runs K single-token decode steps
     from each slot's current token, sampling K candidate tokens (plus
     one catch-up step so the draft cache stays token-synchronized with
     the target's whatever the acceptance outcome).
  2. **verify** — the target model runs ONE cached multi-token forward
     over ``[cur, d_1..d_K]`` (``decode=True``; recurrent families step
     inside the same jit) and the hidden states are consumed logits-free:
     the streaming top-k sampler draws the target's own choice at every
     position, and in rejection mode `kernels/score_tokens` additionally
     gathers ``log p_target(d_i | prefix)`` for every drafted token
     under an online softmax (greedy acceptance is pure argmax
     comparison and skips the scoring pass) — the ``(B, K+1, V)``
     verification logits tensor never exists.
  3. **accept** — greedy mode (temperature == 0) keeps the longest
     prefix of drafts that exactly match the target's argmax; rejection
     mode keeps draft i with probability ``min(1, p_t(d_i)/p_d(d_i))``
     computed from the two scored log-probs — capped logits on both
     sides, each at its model's SAMPLING temperature, so the ratio
     compares the distributions actually drawn from.  Either way the
     step emits the accepted prefix plus one
     token the target itself chose — 1..K+1 tokens, always ≥ 1, and in
     greedy mode every emitted token is the target's argmax, so output
     is token-identical to non-speculative greedy decode.
  4. **roll back** — rejected positions leave both caches: per-slot
     length arithmetic for attention KV caches
     (`registry.rollback_slot_caches`), per-slot snapshot selection for
     recurrent state (`registry.select_step_caches`).

Rejection mode's replacement token is drawn from the target's top-k
distribution at the rejection position (an approximation of the exact
residual distribution, which cannot be formed without the full logits
row), and the acceptance ratio uses the draft's full-softmax log-prob
even when the draft samples through a top-k/top-p truncation; greedy
mode is exact.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import Arch
from repro.kernels.score_tokens import pallas_score_tokens, streaming_score
from repro.models.registry import (apply_mtp_heads, forward_hidden,
                                   init_params, rollback_slot_caches,
                                   rollback_snapshot_caches,
                                   spec_cache_strategy, supports_mtp)
from repro.serve.engine import (Engine, ServeConfig, make_sampler,
                                prefill_last_hidden, resolve_logit_softcap)


@dataclasses.dataclass
class SpecConfig:
    """Speculative-decoding knobs (target-model knobs stay in ServeConfig).

    k: drafted tokens per engine step (a step emits 1..k+1 tokens).
    score_impl: 'pallas' (the score_tokens kernel, interpret mode
        off-TPU) or 'jax' (the streaming_score oracle).
    score_block_v: vocab chunk of the 'jax' scorer.
    draft_temperature: draft proposal temperature; None follows the
        target ServeConfig (greedy target => greedy draft, which is
        what makes self-draft acceptance exact).
    """
    k: int = 4
    score_impl: str = "pallas"
    score_block_v: int = 8192
    draft_temperature: Optional[float] = None


def small_draft(arch: Arch, seed: int = 7, **overrides):
    """(draft_arch, draft_params): a 1-layer, narrow draft of the same
    family sharing `arch`'s vocabulary — the canonical demo/test/bench
    draft shape (real deployments load a separately trained draft).
    Only meaningful for the transformer family's config fields.
    """
    fields = dict(name=arch.cfg.name + "-draft", n_layers=1, d_model=32,
                  num_heads=2, num_kv_heads=1, head_dim=16, d_ff=48)
    fields.update(overrides)
    draft_arch = dataclasses.replace(
        arch, cfg=dataclasses.replace(arch.cfg, **fields))
    return draft_arch, init_params(draft_arch, jax.random.PRNGKey(seed))


def verify_forward(arch: Arch, params, caches, seq, shard, strat):
    """The target's multi-token verification forward over `seq` (B, S).

    ``'len'`` strategy: ONE cached ``decode=True`` forward (per-row
    append); ``'scan'``: S sequential single-token forwards with a cache
    snapshot after each (rollback selects a snapshot per slot).  Returns
    (hiddens (B, S, d), new_caches, snapshots | None).
    """
    if strat == "len":
        h, _, caches = forward_hidden(arch, params, {"tokens": seq},
                                      caches=caches, shard=shard,
                                      decode=True)
        return h, caches, None
    hs, snaps = [], [caches]
    for j in range(seq.shape[1]):
        hj, _, caches = forward_hidden(
            arch, params, {"tokens": seq[:, j:j + 1]},
            caches=caches, shard=shard)
        snaps.append(caches)
        hs.append(hj[:, -1, :])
    return jnp.stack(hs, axis=1), caches, snaps


def build_spec_step(arch: Arch, draft_arch: Arch, sc: ServeConfig,
                    spec: SpecConfig, axes, draft_axes, shard=None):
    """The jit-ready speculative step.

    spec_step(params, dparams, caches, dcaches, cur (B,1), rng) ->
        (tokens (B, K+1) int32, counts (B,) int32, caches, dcaches,
         n_accepted (B,) int32)

    Per slot, ``tokens[:counts]`` are the emitted tokens of this step
    (accepted drafts + the target's bonus/replacement token); positions
    beyond are zero-padded.  Free slots compute garbage that callers
    ignore (every per-row op is batch-diagonal, as in the plain engine).
    """
    k_spec = spec.k
    if k_spec < 1:
        raise ValueError(f"spec.k must be >= 1, got {k_spec}")
    if draft_arch.vocab_size != arch.vocab_size:
        raise ValueError(
            f"draft vocab {draft_arch.vocab_size} != target vocab "
            f"{arch.vocab_size}: draft and target must share a tokenizer")
    valid = arch.vocab_size
    target_cap = resolve_logit_softcap(arch, sc)
    draft_cap = resolve_logit_softcap(draft_arch, sc)
    greedy = sc.temperature == 0.0
    draft_temp = (sc.temperature if spec.draft_temperature is None
                  else spec.draft_temperature)
    t_strat = spec_cache_strategy(arch)
    d_strat = spec_cache_strategy(draft_arch)

    def _score(h2, w, ids, cap, temp, ws=None):
        # scored at the model's SAMPLING temperature, so the rejection
        # ratio compares the distributions actually drawn from (temp <= 0
        # scores unscaled — the degenerate greedy-proposal corner)
        if spec.score_impl == "pallas":
            logp, _ = pallas_score_tokens(h2, w, ids, valid_vocab=valid,
                                          logit_softcap=cap,
                                          temperature=temp, w_scale=ws)
        elif spec.score_impl == "jax":
            logp, _ = streaming_score(h2, w, ids,
                                      block_v=spec.score_block_v,
                                      valid_vocab=valid, logit_softcap=cap,
                                      temperature=temp, w_scale=ws)
        else:
            raise ValueError(f"unknown score impl {spec.score_impl!r}")
        return logp

    sampler_t = make_sampler(arch, sc)
    sampler_d = make_sampler(draft_arch, sc)

    def spec_step(params, dparams, caches, dcaches, cur, rng):
        b = cur.shape[0]
        rngs = jax.random.split(rng, k_spec + 2)
        # quantized-head serving: both engines carry the per-row scales
        # next to their 1-byte lm_head (engine.Engine.__init__)
        t_ws = params.get("lm_head_scale")
        d_ws = dparams.get("lm_head_scale")

        # ---- 1. draft proposal: K sampled tokens + one catch-up step so
        # the draft cache consumes d_K too (kept only if all K accepted)
        d_tokens, d_hidden = [], []
        d_snaps = [dcaches] if d_strat == "scan" else None
        tok = cur                                        # (B, 1)
        for i in range(k_spec + 1):
            h, _, dcaches = forward_hidden(draft_arch, dparams,
                                           {"tokens": tok}, caches=dcaches,
                                           shard=shard)
            if d_snaps is not None:
                d_snaps.append(dcaches)
            if i == k_spec:
                break
            h_last = h[:, -1, :]
            nxt = sampler_d(h_last, dparams["lm_head"], rngs[i],
                            draft_temp, w_scale=d_ws)    # (B,)
            d_hidden.append(h_last)
            d_tokens.append(nxt)
            tok = nxt[:, None]
        draft_tokens = jnp.stack(d_tokens, axis=1)       # (B, K)
        if not greedy:
            # one batched (B*K)-row vocab scan instead of K scans of B
            dh = jnp.stack(d_hidden, axis=1)             # (B, K, d)
            d_lp = _score(dh.reshape(b * k_spec, -1), dparams["lm_head"],
                          draft_tokens.reshape(b * k_spec, 1),
                          draft_cap, draft_temp,
                          ws=d_ws).reshape(b, k_spec)

        # ---- 2. target verification over [cur, d_1..d_K]
        seq = jnp.concatenate([cur, draft_tokens], axis=1)   # (B, K+1)
        h, caches, t_snaps = verify_forward(arch, params, caches, seq,
                                            shard, t_strat)
        d_model = h.shape[-1]

        # the target's own choice at every position (argmax when greedy)
        choice = sampler_t(h.reshape(b * (k_spec + 1), d_model),
                           params["lm_head"], rngs[-1], sc.temperature,
                           w_scale=t_ws).reshape(b, k_spec + 1)

        # ---- 3. acceptance
        if greedy:
            # exact-match needs only the argmax; no scoring pass
            acc = draft_tokens == choice[:, :k_spec]
        else:
            # log p_target(d_i | prefix) — the score_tokens kernel:
            # position i's hidden state scores drafted token i+1
            t_logps = _score(h[:, :k_spec, :].reshape(b * k_spec, d_model),
                             params["lm_head"],
                             draft_tokens.reshape(b * k_spec, 1),
                             target_cap, sc.temperature,
                             ws=t_ws).reshape(b, k_spec)
            u = jax.random.uniform(rngs[-2], (b, k_spec),
                                   minval=1e-20, maxval=1.0)
            acc = jnp.log(u) <= (t_logps - d_lp)         # min(1, pt/pd)
        prefix = jnp.cumprod(acc.astype(jnp.int32), axis=1)
        n_acc = jnp.sum(prefix, axis=1)                  # (B,) in [0, K]

        pos = jnp.arange(k_spec + 1)[None, :]
        dpad = jnp.concatenate(
            [draft_tokens, jnp.zeros((b, 1), draft_tokens.dtype)], axis=1)
        bonus = jnp.take_along_axis(choice, n_acc[:, None], axis=1)
        out = jnp.where(pos < n_acc[:, None], dpad, 0)
        out = jnp.where(pos == n_acc[:, None], bonus, out)
        counts = n_acc + 1

        # ---- 4. roll back the K - n_acc rejected positions (both models
        # consumed K+1 tokens this step and keep n_acc + 1 of them)
        if t_strat == "len":
            caches = rollback_slot_caches(caches, k_spec - n_acc)
        else:
            caches = rollback_snapshot_caches(t_snaps, n_acc + 1,
                                              k_spec - n_acc, axes)
        if d_strat == "len":
            dcaches = rollback_slot_caches(dcaches, k_spec - n_acc)
        else:
            dcaches = rollback_snapshot_caches(d_snaps, n_acc + 1,
                                               k_spec - n_acc, draft_axes)
        return out.astype(jnp.int32), counts.astype(jnp.int32), \
            caches, dcaches, n_acc.astype(jnp.int32)

    return spec_step


class SpecEngine(Engine):
    """Slot engine with a draft-model sidecar and speculative steps.

    The target side is a plain `Engine`; a second internal `Engine`
    owns the draft model's params and batched cache tree (same slot
    count / capacity), so prefill, slot recycling, and cache surgery
    reuse the registry machinery for both models.  `decode_step_multi`
    replaces the one-token step with the draft→verify→accept→rollback
    cycle; the base single-token `decode_step` keeps working (and is
    what `ContinuousScheduler` falls back to for plain engines).
    """

    spec_mode = "sidecar"     # scheduler stats: draft model vs self-MTP
    # speculative steps emit multiple tokens per tick and verify drafts
    # unmasked — the serve/modes.py request modes (constrained masks,
    # beam groups, eval scoring) require the plain one-token engines
    supports_modes = False

    def __init__(self, arch: Arch, params, sc: ServeConfig,
                 draft_arch: Arch, draft_params,
                 spec: Optional[SpecConfig] = None, jit: bool = True):
        self.spec = spec or SpecConfig()
        super().__init__(arch, params, sc, jit=jit)
        self.draft = Engine(draft_arch, draft_params, sc, jit=jit)
        self.draft_arch = draft_arch
        step = build_spec_step(arch, draft_arch, sc, self.spec,
                               self._axes, self.draft._axes)
        dn = ({"donate_argnums": (2, 3)}
              if jit and jax.default_backend() != "cpu" else {})
        self._spec_step = jax.jit(step, **dn) if jit else step

    @property
    def spec_k(self) -> int:
        return self.spec.k

    # -- lifecycle (both cache trees) ---------------------------------------

    def reset(self, seed: int = 0):
        super().reset(seed)
        if hasattr(self, "draft"):                 # absent during __init__
            self.draft.reset(seed)

    def prefill_into_slot(self, slot: int, prompt, frontend_embeds=None
                          ) -> int:
        tok = super().prefill_into_slot(slot, prompt,
                                        frontend_embeds=frontend_embeds)
        # the draft's own first-token sample is discarded — the target's
        # prefill token is the emitted one; this just fills the slot's
        # draft cache with the prompt
        self.draft.prefill_into_slot(slot, prompt,
                                     frontend_embeds=frontend_embeds)
        return tok

    def reset_slot(self, slot: int):
        super().reset_slot(slot)
        self.draft.reset_slot(slot)

    # -- the speculative step -----------------------------------------------

    def decode_step_multi(self) -> Tuple[np.ndarray, np.ndarray]:
        """One draft→verify→accept→rollback cycle for every slot.

        Returns (tokens (B, K+1), counts (B,)): per slot the first
        ``counts`` tokens are this step's emissions, in order."""
        with self._tracer.span("spec.step", cat="spec", mode=self.spec_mode,
                               k=self.spec.k):
            out, counts, self.caches, self.draft.caches, _ = \
                self._spec_step(
                    self.params, self.draft.params, self.caches,
                    self.draft.caches, jnp.asarray(self.cur[:, None]),
                    self._split())
            out = np.asarray(jax.device_get(out), np.int32)
            counts = np.asarray(jax.device_get(counts), np.int32)
        self.cur = out[np.arange(out.shape[0]), counts - 1].copy()
        return out, counts


# ---------------------------------------------------------------------------
# self-speculation from the target's own MTP heads (DESIGN.md §7.2)
# ---------------------------------------------------------------------------


def _score_lp(h2, w, ids, *, valid, cap, temp, spec: SpecConfig, ws=None):
    """log p(ids | h2) under the shared lm_head via the score kernels."""
    if spec.score_impl == "pallas":
        logp, _ = pallas_score_tokens(h2, w, ids, valid_vocab=valid,
                                      logit_softcap=cap, temperature=temp,
                                      w_scale=ws)
    elif spec.score_impl == "jax":
        logp, _ = streaming_score(h2, w, ids, block_v=spec.score_block_v,
                                  valid_vocab=valid, logit_softcap=cap,
                                  temperature=temp, w_scale=ws)
    else:
        raise ValueError(f"unknown score impl {spec.score_impl!r}")
    return logp


def build_self_prefill(arch: Arch, sc: ServeConfig, spec: SpecConfig,
                       shard=None, extend: bool = False):
    """batch=1 prefill that also seeds the slot's MTP draft state.

    prefill(params, slot_caches, batch, true_len, rng) ->
        (tok (1,), draft (K,), draft_lp (K,), caches)

    `tok` is the usual first sampled token; `draft` holds the K head
    proposals for the tokens AFTER it (head h at the last real prompt
    position predicts offset h+1), and `draft_lp` their head log-probs
    (zeros in greedy mode — never consulted).  ``extend=True`` builds
    the cache-EXTENSION variant (paged prefix-hit suffix prefill).
    """
    k_spec = spec.k
    valid = arch.vocab_size
    cap = resolve_logit_softcap(arch, sc)
    greedy = sc.temperature == 0.0
    draft_temp = (sc.temperature if spec.draft_temperature is None
                  else spec.draft_temperature)
    sampler = make_sampler(arch, sc)

    def prefill(params, caches, batch, true_len, rng):
        h_last, caches = prefill_last_hidden(arch, params, caches, batch,
                                             true_len, shard=shard,
                                             decode=extend)
        r_tok, r_draft = jax.random.split(rng)
        w = params["lm_head"]
        ws = params.get("lm_head_scale")
        tok = sampler(h_last, w, r_tok, sc.temperature,
                      w_scale=ws)                                # (1,)
        heads = apply_mtp_heads(arch, params, h_last)            # (1, n, d)
        hh = heads[0, :k_spec]                                   # (K, d)
        draft = sampler(hh, w, r_draft, draft_temp, w_scale=ws)  # (K,)
        if greedy:
            d_lp = jnp.zeros((k_spec,), jnp.float32)
        else:
            d_lp = _score_lp(hh, w, draft[:, None], valid=valid, cap=cap,
                             temp=draft_temp, spec=spec, ws=ws)[:, 0]
        return tok, draft, d_lp, caches

    return prefill


def build_self_spec_step(arch: Arch, sc: ServeConfig, spec: SpecConfig,
                         axes, shard=None):
    """The jit-ready SELF-speculative step: the target model drafts for
    itself through its MTP heads — no sidecar model, no second cache
    tree, no draft catch-up forward (DESIGN.md §7.2).

    self_spec_step(params, caches, cur (B,1), draft (B,K), draft_lp (B,K),
                   rng) ->
        (tokens (B, K+1), counts (B,), caches,
         new_draft (B, K), new_draft_lp (B, K), n_accepted (B,))

    One forward per step: the verification forward over
    ``[cur, d_1..d_K]`` both scores this step's drafts AND produces the
    hidden state whose MTP heads propose the NEXT step's drafts (gathered
    at each slot's accepted position, so head h there predicts offset
    h+1 — exactly the tokens after the bonus token).  Greedy emissions
    are token-identical to plain decode: every accepted draft matched the
    target's own argmax and the bonus IS the target's argmax.

    Rejection mode (temperature > 0) accepts draft i with probability
    ``min(1, p_target(d_i)/p_head(d_i))`` where the head log-prob was
    recorded when the draft was proposed (the previous step); like
    Medusa-style drafting, heads propose each horizon independently of
    the intervening drafts, so sampled-mode output is approximate while
    greedy mode is exact.
    """
    k_spec = spec.k
    if k_spec < 1:
        raise ValueError(f"spec.k must be >= 1, got {k_spec}")
    if not supports_mtp(arch):
        raise ValueError(
            f"self-speculation needs MTP heads: arch {arch.arch_id!r} "
            f"(family {arch.family!r}) has mtp.n_heads="
            f"{arch.mtp.n_heads}")
    if k_spec > arch.mtp.n_heads:
        raise ValueError(
            f"spec.k={k_spec} exceeds the arch's mtp.n_heads="
            f"{arch.mtp.n_heads} (each drafted token needs a head)")
    valid = arch.vocab_size
    cap = resolve_logit_softcap(arch, sc)
    greedy = sc.temperature == 0.0
    draft_temp = (sc.temperature if spec.draft_temperature is None
                  else spec.draft_temperature)
    strat = spec_cache_strategy(arch)

    def _score(h2, w, ids, temp, ws=None):
        return _score_lp(h2, w, ids, valid=valid, cap=cap, temp=temp,
                         spec=spec, ws=ws)

    sampler = make_sampler(arch, sc)

    def self_spec_step(params, caches, cur, draft, draft_lp, rng):
        b = cur.shape[0]
        w = params["lm_head"]
        ws = params.get("lm_head_scale")
        r_choice, r_acc, r_draft = jax.random.split(rng, 3)

        # ---- 1. ONE target forward verifies the pending drafts
        seq = jnp.concatenate([cur, draft], axis=1)          # (B, K+1)
        h, caches, snaps = verify_forward(arch, params, caches, seq,
                                          shard, strat)
        d_model = h.shape[-1]

        # the target's own choice at every position
        choice = sampler(h.reshape(b * (k_spec + 1), d_model), w,
                         r_choice, sc.temperature,
                         w_scale=ws).reshape(b, k_spec + 1)

        # ---- 2. acceptance
        if greedy:
            acc = draft == choice[:, :k_spec]
        else:
            t_lp = _score(h[:, :k_spec, :].reshape(b * k_spec, d_model),
                          w, draft.reshape(b * k_spec, 1),
                          sc.temperature, ws=ws).reshape(b, k_spec)
            u = jax.random.uniform(r_acc, (b, k_spec),
                                   minval=1e-20, maxval=1.0)
            acc = jnp.log(u) <= (t_lp - draft_lp)    # min(1, pt/ph)
        prefix = jnp.cumprod(acc.astype(jnp.int32), axis=1)
        n_acc = jnp.sum(prefix, axis=1)              # (B,) in [0, K]

        pos = jnp.arange(k_spec + 1)[None, :]
        dpad = jnp.concatenate(
            [draft, jnp.zeros((b, 1), draft.dtype)], axis=1)
        bonus = jnp.take_along_axis(choice, n_acc[:, None], axis=1)
        out = jnp.where(pos < n_acc[:, None], dpad, 0)
        out = jnp.where(pos == n_acc[:, None], bonus, out)
        counts = n_acc + 1

        # ---- 3. next step's drafts: MTP heads at the accepted position
        # (hidden after consuming [cur, d_1..d_a] — its trunk choice was
        # the bonus token, so head h there predicts offset h+1 AFTER it)
        h_a = jnp.take_along_axis(
            h, n_acc[:, None, None], axis=1)[:, 0]           # (B, d)
        heads = apply_mtp_heads(arch, params, h_a)           # (B, n, d)
        hh = heads[:, :k_spec].reshape(b * k_spec, d_model)
        new_draft = sampler(hh, w, r_draft, draft_temp,
                            w_scale=ws).reshape(b, k_spec)
        if greedy:
            new_lp = jnp.zeros((b, k_spec), jnp.float32)
        else:
            new_lp = _score(hh, w, new_draft.reshape(b * k_spec, 1),
                            draft_temp, ws=ws).reshape(b, k_spec)

        # ---- 4. roll back the K - n_acc rejected positions
        if strat == "len":
            caches = rollback_slot_caches(caches, k_spec - n_acc)
        else:
            caches = rollback_snapshot_caches(snaps, n_acc + 1,
                                              k_spec - n_acc, axes)
        return (out.astype(jnp.int32), counts.astype(jnp.int32), caches,
                new_draft.astype(jnp.int32), new_lp,
                n_acc.astype(jnp.int32))

    return self_spec_step


class SelfSpecEngine(Engine):
    """Slot engine that speculates with the TARGET model's own MTP heads.

    Versus the sidecar `SpecEngine`: no draft model, no second batched
    cache tree, no per-step draft catch-up forwards — the only extra live
    state is the (B, K) pending-draft token/log-prob arrays, and the only
    extra compute is the K head MLPs at ONE gathered position per slot
    per step.  Prefill seeds each slot's drafts from the heads at the
    last prompt position; every decode step then runs the single
    verify-and-redraft forward of `build_self_spec_step`.
    """

    spec_mode = "self"
    supports_modes = False    # see SpecEngine: multi-token emission

    def __init__(self, arch: Arch, params, sc: ServeConfig,
                 spec: Optional[SpecConfig] = None, jit: bool = True):
        # the default SpecConfig drafts one token per available head; an
        # EXPLICIT spec with k > n_heads is an error (raised by
        # build_self_spec_step below)
        self.spec = spec if spec is not None \
            else SpecConfig(k=max(arch.mtp.n_heads, 1))
        super().__init__(arch, params, sc, jit=jit)
        step = build_self_spec_step(arch, sc, self.spec, self._axes)
        prefill = build_self_prefill(arch, sc, self.spec)
        prefill_ext = build_self_prefill(arch, sc, self.spec, extend=True)
        wrap = jax.jit if jit else (lambda f, **kw: f)
        dn = ({"donate_argnums": (1,)}
              if jit and jax.default_backend() != "cpu" else {})
        self._spec_step = wrap(step, **dn)
        self._prefill_mtp = wrap(prefill)
        self._prefill_mtp_ext = wrap(prefill_ext)

    @property
    def spec_k(self) -> int:
        return self.spec.k

    # -- lifecycle (adds the per-slot pending-draft state) -------------------

    def reset(self, seed: int = 0):
        # self.spec is assigned BEFORE super().__init__ triggers the
        # construction-time reset, so the draft state always exists
        super().reset(seed)
        k = self.spec.k
        self._draft = jnp.zeros((self.sc.batch_size, k), jnp.int32)
        self._draft_lp = jnp.zeros((self.sc.batch_size, k), jnp.float32)

    def reset_slot(self, slot: int):
        super().reset_slot(slot)
        self._draft = self._draft.at[slot].set(0)
        self._draft_lp = self._draft_lp.at[slot].set(0.0)

    def prefill_into_slot(self, slot: int, prompt, frontend_embeds=None
                          ) -> int:
        batch, slot_caches, true_len, ctx = self._slot_prefill_view(
            slot, prompt, frontend_embeds)
        t_b = batch["tokens"].shape[1]
        with self._tracer.span("engine.prefill", cat="engine", slot=slot,
                               tokens=t_b, ext=bool(ctx.get("ext"))):
            fn = (self._prefill_mtp_ext if ctx.get("ext")
                  else self._prefill_mtp)
            tok, draft, d_lp, slot_caches = fn(
                self.params, slot_caches, batch, jnp.int32(true_len),
                self._split())
            self._commit_slot(slot, slot_caches, ctx)
            self._draft = self._draft.at[slot].set(draft)
            self._draft_lp = self._draft_lp.at[slot].set(d_lp)
            tok = int(jax.device_get(tok)[0])
        self._m_prefills.inc()
        self._m_prefill_tokens.inc(t_b)
        self.cur[slot] = tok
        return tok

    # -- the self-speculative step -------------------------------------------

    def decode_step_multi(self) -> Tuple[np.ndarray, np.ndarray]:
        """One verify→accept→redraft→rollback cycle for every slot."""
        with self._tracer.span("spec.step", cat="spec", mode=self.spec_mode,
                               k=self.spec.k):
            (out, counts, self.caches, self._draft, self._draft_lp, _) = \
                self._spec_step(self.params, self.caches,
                                jnp.asarray(self.cur[:, None]),
                                self._draft, self._draft_lp,
                                self._split())
            out = np.asarray(jax.device_get(out), np.int32)
            counts = np.asarray(jax.device_get(counts), np.int32)
        self.cur = out[np.arange(out.shape[0]), counts - 1].copy()
        return out, counts
