"""The train step: forward (fused loss) -> backward -> clip -> update.

The loss is the paper's fused projection+CE.  Implementation selection:

  'auto' / 'streaming' / 'pallas' / 'canonical'
                                         local (per-device full vocab);
                                         'auto' is 'pallas' on a TPU
  'sharded'                              shard_map vocab-TP + row-DP
                                         (paper §3.2.2; '2d' layout)
  'sharded_sp'                           paper-faithful SP->TP gather

The sharded impls need a mesh, and stream each device's panel with the
same local kernel 'auto' picks.

Gradient accumulation: the global batch is split into `grad_accum`
microbatches scanned sequentially, grads accumulated in f32.  Combined
with per-layer remat this bounds activation memory to one microbatch.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import Arch, TuningConfig
from repro.core import fused_cross_entropy, LossConfig
from repro.core.fused_ce import default_impl
from repro.core.windows import BlockPlan
from repro.core.sharded import make_sharded_loss
from repro.models.registry import cast_params, forward_hidden
from repro.optim import make_optimizer, clip_by_global_norm
from repro.optim import schedules as S
from repro.sharding.rules import AxisRules
from repro.train.state import make_train_state, state_shardings


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"
    opt_kwargs: tuple = ()              # tuple of (k, v) for hashability
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    schedule: str = "warmup_cosine"
    max_grad_norm: float = 1.0
    loss_impl: str = "streaming"
    loss_block_v: int = 2048
    label_smoothing: float = 0.0
    z_loss: float = 0.0
    grad_filter_eps: float = 0.0   # skip low-mass vocab tiles in backward
    grad_accum: int = 1
    accum_dtype: str = "float32"   # grad-accumulation buffer dtype
    zero3: bool = False
    tuning: TuningConfig = TuningConfig()   # block-plan autotuning

    def make_schedule(self):
        if self.schedule == "warmup_cosine":
            return S.warmup_cosine(self.peak_lr, self.warmup_steps,
                                   self.total_steps)
        if self.schedule == "warmup_linear":
            return S.warmup_linear(self.peak_lr, self.warmup_steps,
                                   self.total_steps)
        if self.schedule == "warmup_rsqrt":
            return S.warmup_rsqrt(self.peak_lr, self.warmup_steps)
        return S.constant(self.peak_lr)


def _loss_cfg(arch: Arch, tc: TrainConfig) -> LossConfig:
    return arch.loss_config(
        block_v=tc.loss_block_v, label_smoothing=tc.label_smoothing,
        z_loss=tc.z_loss, grad_filter_eps=tc.grad_filter_eps)


def resolve_block_plan(tc: TrainConfig, lcfg: LossConfig, n_rows: int,
                       vocab: int, d: int, dtype) -> Optional[BlockPlan]:
    """Tune-once plan resolution for the train step (None when disabled).

    The first resolution for a given (shape, dtype, backend) key runs the
    autotuner trials; every later call — including re-traces and later
    processes sharing the cache file — is a pure cache hit, so the tuned
    plan is effectively chosen once at startup and reused per step.
    """
    if not tc.tuning.enabled:
        return None
    from repro.kernels.fused_ce.autotune import autotune_plan
    from repro.tuning import get_cache
    t = tc.tuning
    return autotune_plan(
        n_rows, vocab, d, dtype, cfg=lcfg, cache=get_cache(t.cache_path),
        trial_budget=t.trial_budget, trial_iters=t.trial_iters)


def _shard_counts(mesh, rows_axes: Tuple[str, ...],
                  vocab_axis: str = "model") -> Tuple[int, int]:
    """(row shards, vocab shards) of the sharded-loss layout."""
    rows = math.prod(mesh.shape[a] for a in rows_axes) if rows_axes else 1
    return rows, mesh.shape[vocab_axis]


def _streaming_accuracy(rows, w, targets, lcfg: LossConfig) -> jax.Array:
    """Top-1 accuracy over non-ignored rows WITHOUT materializing logits
    (streaming vocab-chunked argmax, stop_gradient — a metric, not a
    loss term)."""
    from repro.serve.sampler import streaming_topk
    rows = jax.lax.stop_gradient(rows)
    w = jax.lax.stop_gradient(w)
    _, ids = streaming_topk(rows, w, 1, block_v=lcfg.block_v,
                            valid_vocab=lcfg.valid_vocab,
                            logit_softcap=lcfg.logit_softcap)
    keep = targets != lcfg.ignore_index
    hit = jnp.sum((ids[:, 0] == targets) & keep)
    return hit / jnp.maximum(jnp.sum(keep), 1)


def build_loss_fn(arch: Arch, tc: TrainConfig,
                  rules: Optional[AxisRules] = None) -> Callable:
    """(params, batch) -> (loss, metrics).

    With `arch.mtp.n_heads > 0` the loss is multi-horizon (DESIGN.md §7.1):
    horizon 0 is the trunk CE on batch['targets']; head h adds its weight
    times the fused CE of the head-h hiddens against the targets shifted
    left by h (IGNORE_INDEX tails).  All horizons share ONE BlockPlan —
    identical (rows, vocab, d, dtype) keys, so the autotuner tunes once —
    and report per-horizon ce_h*/acc_h* metrics.  Zero-weight horizons are
    statically dropped from the total (their gradients are exactly zero)
    but still measured.
    """
    lcfg = _loss_cfg(arch, tc)
    mesh = rules.mesh if rules is not None else None
    shard = rules.shard if rules is not None else None
    n_mtp = arch.mtp.n_heads
    mtp_w = arch.mtp.resolved_weights()

    use_sharded = tc.loss_impl in ("sharded", "sharded_sp")
    if use_sharded and mesh is None:
        raise ValueError(f"loss_impl={tc.loss_impl!r} shards the loss over "
                         "a mesh; pass AxisRules with a mesh (--devices D,M)")
    rows_axes = tuple(a for a in ("pod", "data")
                      if a in mesh.axis_names) if use_sharded else ()
    layout = "sp_gather" if tc.loss_impl == "sharded_sp" else "2d"

    # built lazily at trace time (shapes are concrete there, which is what
    # lets the autotuner key on the per-shard local panel); memoized so the
    # shard_map closures and the tuned plan are constructed exactly once
    sharded_cache: Dict[Tuple[int, int], Callable] = {}

    def sharded_loss(n_rows, vocab, d, dtype):
        key = (n_rows, vocab)
        if key not in sharded_cache:
            n_row_shards, n_vocab_shards = _shard_counts(mesh, rows_axes)
            plan = resolve_block_plan(
                tc, lcfg, n_rows // n_row_shards, vocab // n_vocab_shards,
                d, dtype)
            sharded_cache[key] = make_sharded_loss(
                mesh, lcfg, rows_axes=rows_axes, vocab_axis="model",
                layout=layout, impl=default_impl(), plan=plan)
        return sharded_cache[key]

    def loss_fn(params, batch):
        # one cast of the master weights, the lm_head's included, for the
        # forward and the loss alike (DESIGN.md §14)
        with jax.named_scope("cast"):
            params = cast_params(arch, params)
        if n_mtp:
            h, head_h, aux, _ = forward_hidden(arch, params, batch,
                                               shard=shard,
                                               return_heads=True)
        else:
            h, aux, _ = forward_hidden(arch, params, batch, shard=shard)
            head_h = None
        # the scope holds the fused_ce kernels (repro.obs.SCOPES)
        with jax.named_scope("loss"):
            return loss_of(params, batch, h, head_h, aux)

    def loss_of(params, batch, h, head_h, aux):
        d = h.shape[-1]
        rows = h.reshape(-1, d)
        w = params["lm_head"]

        if use_sharded:
            sfn = sharded_loss(rows.shape[0], w.shape[0], d, rows.dtype)

            def ce_of(r, y):
                return sfn(r, w, y)
        else:
            impl = tc.loss_impl
            plan = None
            if impl in ("streaming", "pallas", "auto"):
                # resolved ONCE; every horizon streams the same panel shape
                plan = resolve_block_plan(tc, lcfg, rows.shape[0],
                                          w.shape[0], d, rows.dtype)

            def ce_of(r, y):
                return fused_cross_entropy(r, w, y, impl=impl, cfg=lcfg,
                                           plan=plan)

        targets0 = batch["targets"].reshape(-1)
        ce0 = ce_of(rows, targets0)
        ce = ce0
        metrics: Dict[str, jax.Array] = {}
        if n_mtp:
            from repro.models.mtp import shift_targets
            metrics["ce_h0"] = ce0
            if arch.mtp.track_accuracy:
                metrics["acc_h0"] = _streaming_accuracy(rows, w, targets0,
                                                        lcfg)
            for hz in range(1, n_mtp + 1):
                tgt = shift_targets(batch["targets"], hz,
                                    lcfg.ignore_index).reshape(-1)
                rows_h = head_h[..., hz - 1, :].reshape(-1, d)
                ce_h = ce_of(rows_h, tgt)
                if mtp_w[hz - 1]:
                    ce = ce + mtp_w[hz - 1] * ce_h
                metrics[f"ce_h{hz}"] = ce_h
                if arch.mtp.track_accuracy:
                    metrics[f"acc_h{hz}"] = _streaming_accuracy(
                        rows_h, w, tgt, lcfg)
        loss = ce + aux
        return loss, dict(metrics, ce=ce, aux=aux)

    return loss_fn


def make_tuning_prewarm(arch: Arch, tc: TrainConfig, n_rows: int,
                        rules: Optional[AxisRules] = None) -> Callable:
    """`on_start` hook for `train_loop`: populate the tuning cache for the
    training shape BEFORE step 0, so trial timing never pollutes the
    compiled step or the per-step timings.  `n_rows` is the GLOBAL batch
    rows (global_batch * seq_len); microbatching is applied here.
    Best-effort — if the traced row count differs (e.g. frontend tokens),
    the trace-time resolution in `build_loss_fn` re-tunes for the exact
    shape.
    """
    def hook():
        if not tc.tuning.enabled:
            return
        lcfg = _loss_cfg(arch, tc)
        dtype = jnp.dtype(getattr(arch.cfg, "compute_dtype", "float32"))
        vocab = arch.padded_vocab
        # the loss sees one microbatch at a time under grad accumulation
        n = n_rows // max(tc.grad_accum, 1)
        mesh = rules.mesh if rules is not None else None
        if tc.loss_impl in ("sharded", "sharded_sp") and mesh is not None:
            rows_axes = tuple(a for a in ("pod", "data")
                              if a in mesh.axis_names)
            n_row_shards, n_vocab_shards = _shard_counts(mesh, rows_axes)
            n, vocab = n // n_row_shards, vocab // n_vocab_shards
        resolve_block_plan(tc, lcfg, n, vocab, arch.cfg.d_model, dtype)
    return hook


def build_train_step(arch: Arch, tc: TrainConfig,
                     rules: Optional[AxisRules] = None):
    """Returns (init_fn(rng) -> state, step_fn(state, batch) -> (state, m)).

    step_fn is NOT jitted here — callers jit with donation + shardings
    (launch/train.py) or lower it for the dry-run (launch/dryrun.py).
    """
    loss_fn = build_loss_fn(arch, tc, rules)
    opt_init, opt_update = make_optimizer(tc.optimizer,
                                          **dict(tc.opt_kwargs))
    sched = tc.make_schedule()

    def init_fn(rng):
        from repro.models.registry import init_params
        params = init_params(arch, rng)
        return make_train_state(params, opt_init)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def constrain_like_params(tree):
        """Pin grad/accumulator shardings to the param layout — without
        this GSPMD may leave the f32 accumulation buffers underpartitioned
        (observed: +30 GiB/device on arctic-480b)."""
        if rules is None or rules.mesh is None:
            return tree
        from repro.sharding.rules import param_specs
        specs = param_specs(tree, rules)
        flat_x, treedef = jax.tree.flatten(tree)
        flat_s = treedef.flatten_up_to(specs)
        out = [jax.lax.with_sharding_constraint(
            x, NamedSharding(rules.mesh, s))
            for x, s in zip(flat_x, flat_s)]
        return jax.tree.unflatten(treedef, out)

    def compute_grads(params, batch):
        if tc.grad_accum <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, constrain_like_params(grads)

        def micro(b):
            return jax.tree.map(
                lambda x: x.reshape((tc.grad_accum,
                                     x.shape[0] // tc.grad_accum)
                                    + x.shape[1:]), b)

        micro_batch = micro(batch)

        acc_dt = jnp.dtype(tc.accum_dtype)

        def body(carry, mb):
            acc, loss_sum, msum = carry
            (loss, metrics), grads = grad_fn(params, mb)
            grads = constrain_like_params(grads)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(acc_dt), acc, grads)
            msum = jax.tree.map(lambda a, m: a + m, msum, metrics)
            return (acc, loss_sum + loss, msum), None

        zero = constrain_like_params(jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dt), params))
        # accumulate the FULL metrics dict (per-horizon MTP entries
        # included), structured from an abstract eval of one microbatch
        first_mb = jax.tree.map(lambda x: x[0], micro_batch)
        m_struct = jax.eval_shape(lambda mb: grad_fn(params, mb)[0][1],
                                  first_mb)
        m_zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              m_struct)
        (acc, loss_sum, msum), _ = jax.lax.scan(
            body, (zero, jnp.zeros(()), m_zero), micro_batch)
        ga = jnp.float32(tc.grad_accum)
        # keep the accumulation dtype: f32(acc)/f32 would silently promote
        # a bf16 accumulator to f32 (full param-sized temps)
        grads = jax.tree.map(lambda g: (g / ga).astype(g.dtype), acc)
        loss = loss_sum / ga
        metrics = jax.tree.map(lambda m: m / ga, msum)
        return loss, metrics, grads

    def step_fn(state, batch):
        loss, metrics, grads = compute_grads(state["params"], batch)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tc.max_grad_norm)
            lr = sched(state["step"])
            new_params, new_opt = opt_update(grads, state["opt"],
                                             state["params"], lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return init_fn, step_fn


def jit_train_step(arch: Arch, tc: TrainConfig, rules: AxisRules,
                   state_example, batch_example_specs: Dict[str, P]):
    """jit with explicit in/out shardings + state donation."""
    _, step_fn = build_train_step(arch, tc, rules)
    st_sh = state_shardings(state_example, rules)
    mesh = rules.mesh
    batch_sh = {k: NamedSharding(mesh, p)
                for k, p in batch_example_specs.items()}
    return jax.jit(
        step_fn,
        in_shardings=(st_sh, batch_sh),
        out_shardings=(st_sh, None),
        donate_argnums=(0,))
