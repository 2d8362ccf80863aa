#!/usr/bin/env python3
"""Run the system's main path once on a TPU and check what comes out.

    python3 chip_smoke.py                # one chip: train, loss, serve
    python3 chip_smoke.py --four-chips   # the vocab-sharded loss, 4 chips

Everything runs in this one process (a chip belongs to one process), on
qwen3-0.6b at its published widths (d=1024, V=151936, 28 layers) with
random weights from a seed, through the entry points a user calls:

  train   `repro.launch.train` takes 5 steps of global batch 8 x 1024
          tokens with the Pallas fused loss.  Every loss is finite, and
          the compiled step calls the forward and backward kernels.
  loss    The Pallas fused CE against the f32 two-stage `canonical` loss
          on the same (8192, 1024) hidden states and the trained lm_head,
          twice: in bf16 as the train step runs it (its plan, and the
          kernels' f32 gradients before the cast to bf16), and in f32.
          The loss and both gradients agree within the tolerances of
          tests/grad_oracle.py in both.
  serve   `repro.launch.serve --paged` answers 8 requests of 16 new
          tokens.  The compiled decode step calls the sample_topk and
          paged_attn kernels, and for one decode step's hidden states the
          top-k kernel's indices equal `lax.top_k` of the dense f32 logits.

`--four-chips` runs only the trainer's data x vocab-parallel loss
(`--devices 1,4 --loss-impl sharded`) and what it is compared with: the
one-chip Pallas loss and lm_head gradient for the same params and batch,
the sharded loss layer against the one-chip kernel on the same inputs,
and the placement of lm_head, a quarter on each chip.

Each phase prints its numbers on lines of its own.  The last line of a
run that passed is one JSON object, {"ok": true, "device": {...}}.  A run
that finds no TPU, or in which a phase fails, exits non-zero without it.
The persistent compilation cache is placed by `repro.launch.compile_cache`
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` here).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.append(os.path.join(_ROOT, "tests"))       # grad_oracle

ARCH = "qwen3-0.6b"
SEED = 0
BATCH, SEQ = 8, 1024
TRAIN_STEPS = 5
SHARDED_STEPS = 3
REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 16, 16
ORACLE_CHUNK = 1024                  # rows per canonical-oracle chunk
LOSS_RTOL = 2e-5                     # tests/test_fused_ce.py, loss parity
# whole-model comparisons across layouts: the (1, 4) mesh also splits the
# bf16 trunk's heads and FFN, so the hidden states themselves differ by
# bf16 reduction order before the loss sees them (on a v5e: 1.9e-6 in
# the step-0 loss, 3.3e-3 of the largest lm_head gradient entry)
MESH_LOSS_RTOL = 1e-4
MESH_GRAD_RTOL = 2e-2                # of the largest |gradient| entry


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def _train_argv(steps: int, *extra: str):
    return ["--arch", ARCH, "--steps", str(steps),
            "--global-batch", str(BATCH), "--seq-len", str(SEQ),
            "--log-every", "1", "--seed", str(SEED), *extra]


def _kernels_of(jitted, *args):
    """Pallas kernel calls in the program `jitted` compiles for `args`."""
    return _kernels_in(jitted.lower(*args).compile())


def _kernels_in(compiled):
    from repro.kernels.pallas_utils import tpu_kernels
    return tpu_kernels(compiled.as_text())


def _has_kernels(kernels, names) -> bool:
    # autodiff prefixes the names of kernels it transposes (jvp_, ...)
    return all(any(n in k for k in kernels) for n in names)


def _batch_spec():
    import jax
    import jax.numpy as jnp
    return {k: jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
            for k in ("tokens", "targets")}


def _batch0(arch):
    from repro.data import DataConfig, SyntheticLM
    return SyntheticLM(DataConfig(vocab_size=arch.vocab_size, seq_len=SEQ,
                                  global_batch=BATCH, seed=SEED)).batch(0)


def _losses(history):
    import numpy as np
    losses = [m["loss"] for _, m in history]
    check(losses and all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return losses


def _step_times(history):
    """(first step's time, compile included; median of the later ones)."""
    t = [m["step_time_s"] for _, m in history]
    return t[0], statistics.median(t[1:])


def _max_dev(a, b):
    """(max |a - b|, that over max |b|) in f32."""
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    d = float(np.max(np.abs(a - b)))
    return d, d / max(float(np.max(np.abs(b))), 1e-30)


def _outside(a, b) -> int:
    """Entries of `a` outside the grad-oracle tolerance around `b`."""
    import numpy as np
    from grad_oracle import GRAD_ATOL, GRAD_RTOL
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return int(np.sum(np.abs(a - b) > GRAD_ATOL + GRAD_RTOL * np.abs(b)))


def _cache_entries(cache: str) -> set:
    return set(os.listdir(cache)) if os.path.isdir(cache) else set()


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_train():
    """Train through the launcher; returns the trained lm_head."""
    import jax
    from repro.launch import train

    t0 = time.perf_counter()
    res = train.run(_train_argv(TRAIN_STEPS, "--loss-impl", "pallas"))
    wall = time.perf_counter() - t0
    losses = _losses(res.history)
    check(len(losses) == TRAIN_STEPS,
          f"{len(losses)} logged steps, want {TRAIN_STEPS}")
    first, median = _step_times(res.history)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    compiled = res.step.lower(res.state, _batch_spec()).compile()
    mem = compiled.memory_analysis()
    kernels = _kernels_in(compiled)
    say(f"[train] losses {losses}")
    say(f"[train] step 0 {first:.3f} s (compile included), later steps "
        f"median {median:.3f} s, phase {wall:.1f} s")
    say(f"[train] peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB)")
    say(f"[train] compiled step: arguments {mem.argument_size_in_bytes} B, "
        f"temporaries {mem.temp_size_in_bytes} B, "
        f"aliased {mem.alias_size_in_bytes} B")
    say(f"[train] tpu_custom_call {len(kernels)}: {kernels}")
    check(len(kernels) >= 3 and _has_kernels(
        kernels, ("fused_ce_fwd", "fused_ce_bwd")),
        f"train step kernels {kernels}")
    return res.state["params"]["lm_head"]


def _canonical_oracle(h, w, y, cfg):
    """(loss, dh, dw) of the f32 canonical loss, in row chunks so the
    (rows, V) f32 logits of one chunk fit beside the rest."""
    import jax
    import jax.numpy as jnp
    from repro.core import canonical_loss

    f32 = jnp.float32
    cfg_sum = dataclasses.replace(cfg, reduction="sum")
    keep = jnp.sum(y != cfg.ignore_index).astype(f32)
    w32 = w.astype(f32)

    @jax.jit
    def chunk(hc, yc, w32):
        return jax.value_and_grad(
            lambda hc, w32: canonical_loss(hc, w32, yc, cfg_sum),
            (0, 1))(hc.astype(f32), w32)

    total, dws, dhs = 0.0, jnp.zeros_like(w32), []
    with jax.default_matmul_precision("highest"):
        for i in range(0, h.shape[0], ORACLE_CHUNK):
            s = slice(i, i + ORACLE_CHUNK)
            loss, (dh, dw) = chunk(h[s], y[s], w32)
            total, dws = total + loss, dws + dw
            dhs.append(dh)
    return total / keep, jnp.concatenate(dhs) / keep, dws / keep


def _kernel_loss(cfg, y):
    """(loss, dh, dw) of the Pallas fused CE as the train step runs it:
    the loss through `fused_cross_entropy`, and the gradients of the
    kernels under the plan it resolves, in f32, before `pallas_loss`
    casts them to the input's dtype."""
    import jax
    import jax.numpy as jnp
    from repro.core import fused_cross_entropy
    from repro.core.streaming import _row_scale
    from repro.core.windows import choose_blocks
    from repro.kernels.fused_ce import kernel as K

    @jax.jit
    def run(h, w):
        plan = choose_blocks(h.shape[0], w.shape[0], h.shape[-1],
                             in_bytes=h.dtype.itemsize)
        loss = fused_cross_entropy(h, w, y, impl="pallas", cfg=cfg)
        lse, _, _ = K.fwd_stats(h, w, y, cfg, plan=plan)
        gamma = _row_scale(jnp.float32(1.0), y, cfg)
        p_coeff = gamma * (1.0 + 2.0 * jnp.float32(cfg.z_loss) * lse)
        dh, dw = K.bwd_grads(h, w, y, lse, gamma, p_coeff, cfg, plan=plan)
        return loss, dh, dw
    return run


def phase_loss(w):
    """Pallas fused CE vs the f32 canonical oracle on the training
    shapes, with bf16 inputs (the train step's path) and with f32 copies
    of the same values."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.registry import get_arch

    arch = get_arch(ARCH)
    cfg = arch.loss_config()
    y = jnp.asarray(_batch0(arch)["targets"].reshape(-1))
    h = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (BATCH * SEQ, arch.cfg.d_model), jnp.bfloat16)
    ref_loss, ref_dh, ref_dw = _canonical_oracle(h, w, y, cfg)
    ref_loss = float(ref_loss)
    run = _kernel_loss(cfg, y)
    results = []
    for dtype in (jnp.bfloat16, jnp.float32):
        args = (h.astype(dtype), w.astype(dtype))
        kernels = _kernels_of(run, *args)
        check(_has_kernels(kernels, ("fused_ce_fwd", "fused_ce_bwd")),
              f"pallas loss kernels {kernels}")
        loss, dh, dw = run(*args)
        loss = float(loss)
        d_dh, r_dh = _max_dev(dh, ref_dh)
        d_dw, r_dw = _max_dev(dw, ref_dw)
        out = (_outside(dh, ref_dh), _outside(dw, ref_dw))
        name = jnp.dtype(dtype).name
        say(f"[loss] {name} h {h.shape} lm_head {w.shape}: pallas {loss!r} "
            f"canonical {ref_loss!r} |diff| {abs(loss - ref_loss)!r}")
        say(f"[loss] {name} max|d dh| {d_dh!r} ({r_dh!r} of max|dh|), "
            f"max|d dw| {d_dw!r} ({r_dw!r} of max|dw|); entries outside "
            f"the oracle's tolerance: dh {out[0]}, dw {out[1]}")
        results.append((name, loss, out))
        del dh, dw
    for name, loss, out in results:
        np.testing.assert_allclose(loss, ref_loss, rtol=LOSS_RTOL,
                                   err_msg=f"{name} loss")
        check(out == (0, 0), f"{name} gradients: {out} entries outside "
              "the oracle's tolerance")


def phase_serve():
    """Paged serving through the launcher, then its kernels' checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.sample_topk import pallas_topk
    from repro.launch import serve
    from repro.models.registry import forward_hidden

    t0 = time.perf_counter()
    res = serve.run(["--arch", ARCH, "--paged", "--batch", str(REQUESTS),
                     "--requests", str(REQUESTS),
                     "--prompt-len", str(PROMPT_LEN),
                     "--max-new", str(NEW_TOKENS), "--seed", str(SEED)])
    wall = time.perf_counter() - t0
    eng = res.engine
    vocab = eng.arch.vocab_size
    counts = [len(t) for t in res.scheduler.results.values()]
    check(counts == [NEW_TOKENS] * REQUESTS,
          f"tokens per request {counts}")
    check(bool(np.all((res.out >= 0) & (res.out < vocab))),
          "token ids outside the vocabulary")
    say(f"[serve] {len(counts)} requests x {NEW_TOKENS} tokens in "
        f"{wall:.1f} s (compile included); first row {res.out[0].tolist()}")

    tokens = jnp.asarray(eng.cur[:, None])
    kernels = _kernels_of(eng._decode, eng.params, eng.caches, tokens,
                          jax.random.PRNGKey(SEED))
    say(f"[serve] decode step tpu_custom_call {len(kernels)}: "
        f"{sorted(set(kernels))}")
    check(_has_kernels(kernels, ("sample_topk", "paged_attn")),
          f"decode step kernels {kernels}")

    # one decode step's hidden states, through the engine's paged caches
    h = jax.jit(lambda p, c, t: forward_hidden(
        eng.arch, p, {"tokens": t}, caches=c)[0][:, -1, :])(
            eng.params, eng.caches, tokens)
    w = eng.params["lm_head"]
    dense = jnp.dot(h.astype(jnp.float32), w.astype(jnp.float32).T,
                    precision=jax.lax.Precision.HIGHEST)
    dense = jnp.where(jnp.arange(w.shape[0]) < vocab, dense, -jnp.inf)
    for k in (1, eng.sc.top_k):
        _, idx = jax.jit(lambda h, w, k=k: pallas_topk(
            h, w, k, valid_vocab=vocab))(h, w)
        _, ref = jax.lax.top_k(dense, k)
        same = np.asarray(idx) == np.asarray(ref)
        say(f"[serve] top-{k} kernel vs lax.top_k of dense f32 logits: "
            f"{int(same.sum())}/{same.size} indices equal")
        check(bool(same.all()), f"top-{k} indices differ")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips():
    """The trainer's (1, 4) data x vocab-parallel loss vs one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from grad_oracle import assert_grads_close
    from repro.core import fused_cross_entropy
    from repro.core.sharded import make_sharded_loss
    from repro.launch import train
    from repro.launch.mesh import make_local_mesh
    from repro.models.registry import get_arch, init_params
    from repro.sharding.rules import AxisRules, param_shardings
    from repro.train.step import TrainConfig, build_loss_fn

    t0 = time.perf_counter()
    res = train.run(_train_argv(SHARDED_STEPS, "--devices", "1,4",
                                "--loss-impl", "sharded"))
    wall = time.perf_counter() - t0
    losses = _losses(res.history)
    first, median = _step_times(res.history)
    say(f"[4chip] losses {losses}")
    say(f"[4chip] step 0 {first:.3f} s (compile included), later steps "
        f"median {median:.3f} s, phase {wall:.1f} s")
    kernels = _kernels_of(res.step, res.state, _batch_spec())
    say(f"[4chip] tpu_custom_call {len(kernels)}: {kernels}")
    check(_has_kernels(kernels, ("fused_ce_fwd", "fused_ce_bwd")),
          f"sharded train step kernels {kernels}")

    arch = get_arch(ARCH)
    lm = res.state["params"]["lm_head"]
    shards = lm.addressable_shards
    quarter = (arch.padded_vocab // 4, arch.cfg.d_model)
    say(f"[4chip] lm_head {lm.shape}: "
        + ", ".join(f"{s.device.id}:{s.data.shape}" for s in shards))
    check(len({s.device.id for s in shards}) == 4
          and all(s.data.shape == quarter for s in shards),
          "lm_head is not split a quarter per chip")
    del res, lm, shards

    # the same params and batch on one chip, and on the run's mesh
    mesh = make_local_mesh(1, 4)
    rules = AxisRules(mesh=mesh)
    one = jax.devices()[0]
    params = jax.device_put(init_params(arch, jax.random.PRNGKey(SEED)), one)
    batch = {k: jnp.asarray(v) for k, v in _batch0(arch).items()}
    tc = TrainConfig(loss_impl="pallas",
                     loss_block_v=min(2048, arch.padded_vocab))
    grad_one = jax.jit(jax.value_and_grad(build_loss_fn(arch, tc),
                                          has_aux=True))
    (loss1, _), g1 = grad_one(params, batch)
    loss1 = float(loss1)
    tc4 = dataclasses.replace(tc, loss_impl="sharded")
    grad_four = jax.jit(jax.value_and_grad(build_loss_fn(arch, tc4, rules),
                                           has_aux=True))
    params4 = jax.device_put(params, param_shardings(params, rules))
    batch4 = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    (loss4, _), g4 = grad_four(params4, batch4)
    d_g, r_g = _max_dev(g4["lm_head"], g1["lm_head"])
    say(f"[4chip] step-0 loss: run {losses[0]!r}, same params and batch "
        f"on 1 chip {loss1!r}, on the mesh {float(loss4)!r}")
    say(f"[4chip] lm_head grad mesh vs 1 chip: max|diff| {d_g!r} "
        f"({r_g!r} of max|grad|)")
    for name, got in (("run", losses[0]), ("mesh", float(loss4))):
        check(abs(got - loss1) <= MESH_LOSS_RTOL * abs(loss1),
              f"{name} step-0 loss {got} vs one chip {loss1}")
    check(r_g <= MESH_GRAD_RTOL, f"lm_head grad deviates by {r_g}")
    del g1, g4, params4

    # the loss layer alone: same hidden states and lm_head, sharded vs not
    cfg = arch.loss_config()
    y = batch["targets"].reshape(-1)
    h = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (BATCH * SEQ, arch.cfg.d_model), jnp.bfloat16)
    w = params["lm_head"]
    sharded = make_sharded_loss(mesh, cfg, rows_axes=("data",),
                                impl="pallas")
    f4 = jax.jit(jax.value_and_grad(sharded, (0, 1)))
    args4 = (jax.device_put(h, NamedSharding(mesh, P("data", None))),
             jax.device_put(w, NamedSharding(mesh, P("model", None))),
             jax.device_put(y, NamedSharding(mesh, P("data"))))
    kernels = _kernels_of(f4, *args4)
    check(_has_kernels(kernels, ("fused_ce_fwd", "fused_ce_bwd")),
          f"sharded loss kernels {kernels}")
    l4, (dh4, dw4) = f4(*args4)
    l1, (dh1, dw1) = jax.jit(jax.value_and_grad(
        lambda h, w: fused_cross_entropy(h, w, y, impl="pallas", cfg=cfg),
        (0, 1)))(h, w)
    say(f"[4chip] loss layer on the same inputs: sharded {float(l4)!r} "
        f"one chip {float(l1)!r}; max|d dh| {_max_dev(dh4, dh1)[0]!r}, "
        f"max|d dw| {_max_dev(dw4, dw1)[0]!r}")
    np.testing.assert_allclose(float(l4), float(l1), rtol=LOSS_RTOL)
    assert_grads_close((dh4, dw4), (dh1, dw1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the main path once on a TPU and check it.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4)-mesh sharded loss and what "
                         "it is compared with, on four chips of one host")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              "the root of a checkout", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "no phase was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: {len(devices)} chip(s), this run needs {want}",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    entries = _cache_entries(cache)
    say(f"[smoke] {dev.device_kind} x {len(devices)}; compile cache {cache} "
        f"({len(entries)} entries)")

    def timed(phase, *args):
        t0 = time.perf_counter()
        out = phase(*args)
        say(f"[smoke] {phase.__name__} passed in "
            f"{time.perf_counter() - t0:.1f} s")
        return out

    if args.four_chips:
        timed(phase_four_chips)
    else:
        lm_head = timed(phase_train)
        timed(phase_loss, lm_head)
        del lm_head
        timed(phase_serve)
    new = _cache_entries(cache) - entries
    # an entry is <program>-<key hash>-cache, beside its -atime file
    programs = sorted({e.rsplit("-", 1)[0] for e in new})
    say(f"[smoke] compile cache entries {len(entries)} -> "
        f"{len(entries) + len(new)}; programs compiled afresh: {programs}")
    say(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
