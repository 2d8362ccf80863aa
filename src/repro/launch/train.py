"""Training launcher (runs REAL steps on the local devices).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --steps 50 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt

``--devices D,M`` trains on a (data, model) mesh of the host's devices:
its real chips on a TPU host (``--devices 1,4`` splits the model and the
vocabulary over four chips), forced host devices on the CPU (set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax starts).
Without it, one device.  ``--loss-impl auto`` (the default) runs the Pallas
fused-CE kernels on a TPU and the streaming loss elsewhere; ``sharded``
needs ``--devices``.

``--stats-json [PATH]`` dumps the logged step history as JSON;
``--metrics-json [PATH]`` enables `repro.obs` and dumps the step-time,
loss, ``compile.*`` and ``attn.*`` (attention call sites by path)
instruments; ``--trace-out PATH`` records a
``train.step`` span per step holding ``train.feed``, ``train.dispatch``
and ``train.wait`` (bridged to the profiler's annotations so host spans
line up with device profiles) — see DESIGN.md §11.
"""

from __future__ import annotations

import argparse
import logging
from typing import Any, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint import Checkpointer
from repro.configs.base import TuningConfig, with_mtp
from repro.data import DataConfig, SyntheticLM, ShardedLoader
from repro.distributed.fault import PreemptionHandler, StragglerMonitor
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.registry import get_arch
from repro.sharding.rules import AxisRules
from repro.train import (TrainConfig, build_train_step, train_loop,
                         resume_or_init, state_shardings)
from repro.train.step import make_tuning_prewarm


class TrainRun(NamedTuple):
    """What one run leaves: the final state, the logged history and the
    jitted step it ran (``step.lower(state, batch)`` gives its program)."""
    state: Any
    history: List[Tuple[int, dict]]
    step: Any


def run(argv=None) -> TrainRun:
    """Parse `argv` as the command line, train, and return the run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adafactor"))
    ap.add_argument("--loss-impl", default="auto",
                    choices=("auto", "streaming", "pallas", "canonical",
                             "sharded"))
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--grad-filter-eps", type=float, default=0.0,
                    help="gradient-filtered backward: skip vocab tiles "
                         "whose total softmax mass is provably < eps "
                         "(0 = exact; target tiles are never skipped)")
    ap.add_argument("--mtp-heads", type=int, default=0,
                    help="multi-token-prediction heads trained over the "
                         "trunk (per-horizon fused CE, shared BlockPlan)")
    ap.add_argument("--mtp-depth", type=int, default=1,
                    help="residual MLP blocks per MTP head")
    ap.add_argument("--mtp-weights", default=None,
                    help="comma-separated per-head loss weights "
                         "(default: 1.0 each)")
    ap.add_argument("--autotune", action="store_true",
                    help="empirically tune the fused-CE block plan at "
                         "startup (memoized in the tuning cache)")
    ap.add_argument("--tuning-cache", default=None,
                    help="tuning-cache JSON path ('' = in-memory only; "
                         "default: $REPRO_TUNING_CACHE or ~/.cache/repro)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--devices", default=None,
                    help="D,M (data, model) mesh over the host's chips on "
                         "a TPU, over forced host devices on the CPU")
    ap.add_argument("--stats-json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="dump the logged step history (loss, step time) "
                         "as JSON (stdout when PATH is omitted)")
    ap.add_argument("--metrics-json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="enable the repro.obs registry and dump every "
                         "instrument's snapshot, the compile.* counters "
                         "included, as JSON (stdout when PATH is "
                         "omitted)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing of each train.step and "
                         "its train.feed / train.dispatch / train.wait "
                         "(bridged to jax.profiler annotations) and write "
                         "the trace to PATH")
    ap.add_argument("--trace-format", default="chrome",
                    choices=("chrome", "jsonl"),
                    help="trace export format for --trace-out")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    # obs must be live before train_loop binds its instruments
    if args.metrics_json is not None or args.trace_out is not None:
        obs.enable(trace=args.trace_out is not None,
                   jax_annotate=args.trace_out is not None)

    arch = get_arch(args.arch, reduced=args.reduced)
    if args.mtp_heads:
        weights = tuple(float(w) for w in args.mtp_weights.split(",")) \
            if args.mtp_weights else ()
        arch = with_mtp(arch, args.mtp_heads, head_depth=args.mtp_depth,
                        loss_weights=weights, track_accuracy=True)
    mesh = None
    rules = None
    if args.devices:
        d, m = (int(x) for x in args.devices.split(","))
        mesh = make_local_mesh(d, m)
        rules = AxisRules(mesh=mesh)

    tc = TrainConfig(
        optimizer=args.optimizer, peak_lr=args.lr,
        warmup_steps=max(args.steps // 10, 1), total_steps=args.steps,
        loss_impl=args.loss_impl,
        loss_block_v=min(2048, arch.padded_vocab),
        grad_accum=args.grad_accum,
        grad_filter_eps=args.grad_filter_eps,
        tuning=TuningConfig(enabled=args.autotune,
                            cache_path=args.tuning_cache))
    init_fn, step_fn = build_train_step(arch, tc, rules)

    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    shardings = None
    if mesh is not None:
        example = jax.eval_shape(init_fn,
                                 jax.ShapeDtypeStruct((2,), jnp.uint32))
        shardings = state_shardings(example, rules)
    state = resume_or_init(ck, init_fn, jax.random.PRNGKey(args.seed),
                           shardings=shardings)
    if mesh is not None:
        jstep = jax.jit(step_fn, in_shardings=(shardings, None),
                        out_shardings=(shardings, None),
                        donate_argnums=(0,))
    else:
        jstep = jax.jit(step_fn, donate_argnums=(0,))

    dc = DataConfig(vocab_size=arch.vocab_size, seq_len=args.seq_len,
                    global_batch=args.global_batch, seed=args.seed)
    loader = ShardedLoader(SyntheticLM(dc), mesh=mesh)

    on_start = None
    if args.autotune:
        on_start = make_tuning_prewarm(
            arch, tc, n_rows=args.global_batch * args.seq_len, rules=rules)

    state, history = train_loop(
        state=state, step_fn=jstep, data=loader, num_steps=args.steps,
        checkpointer=ck, checkpoint_every=args.ckpt_every,
        log_every=args.log_every,
        preemption=PreemptionHandler(), straggler=StragglerMonitor(),
        on_start=on_start)
    if history:
        first = history[0][1]["loss"]
        last = history[-1][1]["loss"]
        print(f"[train] loss {first:.4f} -> {last:.4f} over "
              f"{len(history)} logged steps")
    if args.stats_json is not None:
        obs.export.dump_json(
            {"arch": arch.arch_id, "steps": args.steps,
             "history": [{"step": i, **m} for i, m in history]},
            args.stats_json, label="stats", tag="train")
    if args.metrics_json is not None:
        obs.export.dump_json(
            obs.export.metrics_report(obs.get_registry(),
                                      extra={"arch": arch.arch_id}),
            args.metrics_json, label="metrics", tag="train")
    if args.trace_out is not None:
        obs.export.write_trace(obs.get_tracer(), args.trace_out,
                               fmt=args.trace_format, tag="train")
    return TrainRun(state, history, jstep)


def main(argv=None):
    """Train; returns (final state, logged history)."""
    res = run(argv)
    return res.state, res.history


if __name__ == "__main__":
    use_compile_cache()
    main()
