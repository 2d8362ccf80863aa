"""Fault-tolerant training loop: resume -> train -> checkpoint -> repeat.

Wires together: data loader, jitted train step, async checkpointer,
preemption handler, straggler monitor.  Single-host here; the multi-host
story is identical modulo `jax.process_index()` plumbing already present
in the checkpointer/data layers.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint import Checkpointer
from repro.distributed.fault import PreemptionHandler, StragglerMonitor

log = logging.getLogger("repro.train")


def train_loop(
    *,
    state,
    step_fn: Callable,
    data: Iterable,
    num_steps: int,
    checkpointer: Optional[Checkpointer] = None,
    checkpoint_every: int = 100,
    log_every: int = 10,
    preemption: Optional[PreemptionHandler] = None,
    straggler: Optional[StragglerMonitor] = None,
    metrics_hook: Optional[Callable[[int, Dict[str, float]], None]] = None,
    on_start: Optional[Callable[[], Any]] = None,
):
    """Runs up to `num_steps` steps; returns (state, history).

    `on_start` is a one-time startup hook run before the first step — the
    intended use is block-plan autotuning (`train.step.make_tuning_prewarm`)
    so kernel trial timing happens once here, outside the recorded per-step
    timings; its wall time is logged separately.

    Each step is a `train.step` span (a `StepTraceAnnotation` when the
    tracer bridges to the profiler) holding `train.feed` (the loader's
    next batch), `train.dispatch` (the `step_fn` call) and `train.wait`
    (the wait on the loss); logging and checkpoint saves are
    `train.log` and `train.checkpoint`.
    """
    preemption = (preemption or PreemptionHandler()).install()
    straggler = straggler or StragglerMonitor()
    history = []
    start_step = int(jax.device_get(state["step"]))

    reg = obs.get_registry()
    tracer = obs.get_tracer()
    m_step_t = reg.histogram("train.step_time_s",
                             help="wall-clock per optimizer step")
    m_loss = reg.gauge("train.loss", help="loss at last logged step")
    m_steps = reg.counter("train.steps_total", help="optimizer steps run")
    m_tokens = reg.counter("train.tokens_total",
                           help="tokens consumed by training")

    if on_start is not None:
        t0 = time.perf_counter()
        on_start()
        log.info("startup hook finished in %.2fs",
                 time.perf_counter() - t0)

    it = iter(data)
    for i in range(start_step, num_steps):
        t0 = time.perf_counter()
        with tracer.step_span("train.step", i):
            with tracer.span("train.feed"):
                batch = next(it)
            with tracer.span("train.dispatch"):
                state, metrics = step_fn(state, batch)
            # block for accurate step timing (and to surface async
            # errors here)
            with tracer.span("train.wait"):
                jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        straggler.record(i, dt)
        m_step_t.observe(dt)
        m_steps.inc()
        n_tok = getattr(batch.get("tokens"), "size", 0) \
            if isinstance(batch, dict) else 0
        if n_tok:
            m_tokens.inc(n_tok)

        if (i + 1) % log_every == 0 or i == start_step:
            with tracer.span("train.log"):
                m = {k: float(np.asarray(jax.device_get(v)))
                     for k, v in metrics.items()}
                m["step_time_s"] = dt
                if "loss" in m:
                    m_loss.set(m["loss"])
                history.append((i, m))
                log.info("step %d: %s", i,
                         {k: round(v, 5) for k, v in m.items()})
                if metrics_hook:
                    metrics_hook(i, m)

        if checkpointer and ((i + 1) % checkpoint_every == 0
                             or preemption.should_stop):
            with tracer.span("train.checkpoint"):
                checkpointer.save_async(i + 1, state)

        if preemption.should_stop:
            log.warning("preempted at step %d — checkpoint flushed", i)
            break

    if checkpointer:
        checkpointer.wait()
    return state, history


def resume_or_init(checkpointer: Optional[Checkpointer], init_fn,
                   rng, shardings=None):
    """Restore the latest checkpoint if present, else init fresh —
    straight into `shardings` when given, so no device ever holds more
    than its own shard of the state."""
    if checkpointer is not None and checkpointer.latest_step() is not None:
        example = jax.eval_shape(init_fn, rng)
        state, step = checkpointer.restore(example, shardings=shardings)
        log.info("resumed from step %d", step)
        return state
    if shardings is not None:
        return jax.jit(init_fn, out_shardings=shardings)(rng)
    return init_fn(rng)
