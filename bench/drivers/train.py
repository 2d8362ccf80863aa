"""The training driver: the program's train loop over its own step.

Set-up builds one object, the jitted step of `repro.train.step` with its
state (weights from `bench/weights.py`, AdamW state from the program), fed
by the program's `ShardedLoader` over the benchmark's `TrainSource`, as
`repro.launch.train` builds them.  Steps 1-3 run through
`repro.train.loop.train_loop` in set-up (the first compiles) and give the
readings the reference is compared with; the same step, state and feed
then run the measured window, again through `train_loop`, which a
deadline stops.  The loop waits on each step's loss; the step it is
handed returns the loss of the step `AHEAD` calls before, so that many
steps stay queued on the chip and a stall of the host's does not leave
it idle.  The window ends when the deadline has passed: nothing more is
sent, every step sent is waited for, and then the clock is read.
`train_tokens_per_s` is every target token of the window's steps over
the window's wall time.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic_gen, weights

# the program's first steps run in set-up; the reference follows them
FIRST_STEPS = 3
# steps queued on the chip beyond the one the loop waits for (2.7 s each)
AHEAD = 2


class Program:
    """The system under test for one cell, built once per process."""

    def __init__(self, cell: dict):
        from repro.optim import make_optimizer
        from repro.train import TrainConfig, build_train_step

        model, mix = cell["model"], cell["mix"]
        self.spec = weights.model_spec(model)
        self.mix = mix
        self.recipe = mix["recipe"]
        self.arch = build_arch(model)
        r = self.recipe
        self.tc = TrainConfig(
            optimizer="adamw", peak_lr=r["peak_lr"],
            opt_kwargs=tuple((k, r[k]) for k in
                             ("b1", "b2", "eps", "weight_decay")),
            warmup_steps=r["warmup_steps"], total_steps=r["total_steps"],
            schedule="warmup_cosine", max_grad_norm=r["max_grad_norm"],
            loss_impl=mix["loss_impl"],
            loss_block_v=min(2048, self.arch.padded_vocab))
        init_fn, step_fn = build_train_step(self.arch, self.tc, None)
        opt_init, _ = make_optimizer("adamw", **dict(self.tc.opt_kwargs))
        spec = self.spec

        def make_state(key):
            from repro.train.state import make_train_state
            return make_train_state(weights.make_params(spec, key), opt_init)

        self.make_state = jax.jit(make_state)
        self.make_params = jax.jit(lambda k: weights.make_params(spec, k))
        self.step = jax.jit(step_fn, donate_argnums=(0,))
        self.tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])
        check_layout(init_fn, spec)


def build_arch(model: dict):
    """The program's architecture `model["arch"]` with the configuration
    file's `overrides` applied to its family config."""
    import dataclasses
    from repro.models.registry import get_arch
    arch = get_arch(model["arch"])
    over = model.get("overrides", {})
    return dataclasses.replace(arch, cfg=dataclasses.replace(arch.cfg, **over))


def check_layout(init_fn, spec):
    """The benchmark's weights have the program's tree, shapes and types."""
    want = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
    got = jax.eval_shape(lambda: weights.make_params(
        spec, jax.random.PRNGKey(0)))
    ws = jax.tree.map(lambda s: (s.shape, str(s.dtype)), want["params"])
    gs = jax.tree.map(lambda s: (s.shape, str(s.dtype)), got)
    if ws != gs:
        raise ValueError(f"weights layout {gs} is not the program's {ws}")


class _Deadline:
    """A `PreemptionHandler` stand-in whose stop request is a deadline."""

    def __init__(self, at: float):
        self.at = at

    def install(self):
        return self

    @property
    def should_stop(self) -> bool:
        return time.perf_counter() >= self.at


class _Ahead:
    """The compiled step, handing the loop the metrics of the step `lag`
    calls back (the first calls: the first step's).  `sent` keeps every
    step's metrics, on the device, in order."""

    def __init__(self, step, lag: int):
        self.step, self.lag, self.sent = step, lag, []

    def __call__(self, state, batch):
        state, metrics = self.step(state, batch)
        self.sent.append(metrics)
        return state, self.sent[max(0, len(self.sent) - 1 - self.lag)]


def _loop(step, state, feed, num_steps, log_every, preemption=None):
    from repro.distributed.fault import PreemptionHandler, StragglerMonitor
    from repro.train import train_loop
    return train_loop(state=state, step_fn=step, data=feed,
                      num_steps=num_steps, log_every=log_every,
                      preemption=preemption or PreemptionHandler(),
                      straggler=StragglerMonitor())


def start(prog: Program, seed: int):
    """Fresh state and feed for `seed`; runs the first steps through the
    train loop and returns (step, state, feed, loader, readings), `step`
    the loop's step that the window goes on with."""
    from repro.data import ShardedLoader
    key = weights.seed_key(seed)
    state = prog.make_state(key)
    source = traffic_gen.TrainSource(prog.mix, prog.spec["vocab"], seed)
    loader = ShardedLoader(source)
    feed = iter(loader)
    step = _Ahead(prog.step, AHEAD)
    state, _ = _loop(step, state, feed, 1, 1)
    b1 = prog.recipe["b1"]
    first = reference.leaf_norms(state["opt"]["mu"]) / (1 - b1)
    state, _ = _loop(step, state, feed, FIRST_STEPS, 1)
    losses = [float(m["loss"]) for m in step.sent[:FIRST_STEPS]]
    p0 = prog.make_params(key)
    change = reference.diff_norms(state["params"], p0)
    del p0
    readings = {"losses": np.array(losses, np.float64),
                "first_grad": first, "change": change,
                "leaves": leaf_names(state["params"])}
    return step, state, feed, loader, readings


def window(step, state, feed, seconds: float,
           trace_dir: Optional[str] = None):
    """The measured window; returns (state, steps, wall seconds)."""
    step0 = int(jax.device_get(state["step"]))
    with traced(trace_dir):
        t0 = time.perf_counter()
        state, _ = _loop(step, state, feed, 1 << 40, 1 << 40,
                         preemption=_Deadline(t0 + seconds))
        jax.block_until_ready(state)
        wall = time.perf_counter() - t0
    steps = int(jax.device_get(state["step"])) - step0
    return state, steps, wall


@contextlib.contextmanager
def traced(trace_dir: Optional[str]):
    """With a directory: the profiler on, and the window a host span
    `bench.window` that the trace reduction finds.  Host Python calls
    are not traced."""
    if not trace_dir:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()


def leaf_names(tree):
    """The path of every leaf, in `jax.tree.leaves` order."""
    return [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def free_device():
    """Drop every array on the device (the program's state, its feed)."""
    for a in jax.live_arrays():
        a.delete()


def reference_readings(prog: Program, seed: int, compute="float32",
                       steps=FIRST_STEPS, **kw) -> Dict[str, Any]:
    """The plain reference over the same weights and the same batches."""
    src = traffic_gen.TrainSource(prog.mix, prog.spec["vocab"], seed)
    batches = []
    for s in range(steps):
        b = src.batch(s)
        batches.append((b["tokens"], b["targets"]))
    p0 = jax.jit(lambda k: weights.make_params(prog.spec, k))(
        weights.seed_key(seed))
    with jax.default_matmul_precision("highest"):
        return reference.train_readings(p0, batches, prog.spec, prog.recipe,
                                        compute=compute, steps=steps, **kw)


def compare(prog_r: dict, ref_r: dict) -> Dict[str, float]:
    """The numbers compared.

    `loss_gap`: the largest relative gap of the first steps' losses.
    `first_grad_gap` and `change_gap`, by the worst leaf: the gap between
    the program's and the reference's norm of the first gradient as the
    optimizer receives it, and of the parameters' change over the first
    steps, each against the larger of the reference's norm of that leaf
    and of the median leaf.  Leaves whose first gradient in the reference
    is under a thousandth of the median leaf's are left out of the change:
    they move by round-off alone.  Each step's loss gap, the median leaf's
    change gap and the worst leaf of each gap are reported beside them
    (`loss_gap_steps`, `change_gap_median`, `first_grad_worst`,
    `change_worst`) and not held to a limit."""
    n = min(len(prog_r["losses"]), len(ref_r["losses"]))
    lp, lr = prog_r["losses"][:n], ref_r["losses"][:n]
    rel = np.abs(lp - lr) / np.abs(lr)

    def gaps(a, b, keep):
        a, b = a[keep], b[keep]
        return np.abs(a - b) / np.maximum(b, np.median(b))

    raw = ref_r["first_grad_raw"]
    moving = raw >= 1e-3 * np.median(raw)
    change = gaps(prog_r["change"], ref_r["change"], moving)
    first = gaps(prog_r["first_grad"], ref_r["first_grad"],
                 np.ones_like(moving))
    names = prog_r.get("leaves") or [str(i) for i in range(len(raw))]
    return {"loss_gap": float(np.max(rel)),
            "first_grad_gap": float(np.max(first)),
            "change_gap": float(np.max(change)),
            "first_grad_worst": names[int(np.argmax(first))],
            "change_worst": [n for n, m in zip(names, moving)
                             if m][int(np.argmax(change))],
            "loss_gap_steps": [float(x) for x in rel],
            "change_gap_median": float(np.median(change)),
            "leaves_left_out": int(np.sum(~moving))}


def run_cell(cell: dict, seed: int, seconds: float, devs, t_start: float,
             trace_dir: Optional[str]) -> Dict[str, Any]:
    from bench import harness, trace_reduce
    prog = Program(cell)
    step, state, feed, loader, prog_r = start(prog, seed)
    setup_s = time.perf_counter() - t_start
    state, steps, wall = window(step, state, feed, seconds, trace_dir)
    device = harness.device_info(devs)
    tokens = steps * prog.tokens_per_step
    breakdown = None
    if trace_dir:
        red = trace_reduce.reduce(trace_reduce.load_xplane(trace_dir))
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        ctx = {"trace": red, "spec": prog.spec, "mix": prog.mix,
               "peaks": harness.peaks(device["kind"]), "chips": len(devs),
               "steps": steps, "tokens": tokens, "wall_s": wall}
        metrics = harness.read_layer_metrics(cell["per_layer"], ctx)
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    else:
        metrics = {
            "train_tokens_per_s": {"value": tokens / wall,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    loader.close()
    del state, feed
    free_device()
    ref_r = reference_readings(prog, seed)
    nums = compare(prog_r, ref_r)
    checks = harness.Checks()
    for name, limit in harness.limits(cell["workload"]["name"]).items():
        checks.add(name, nums[name], limit)
    return {"correct": checks.correct, "attempted": steps, "failed": 0,
            "metrics": metrics, "device": device, "checks": checks,
            "breakdown": breakdown}
