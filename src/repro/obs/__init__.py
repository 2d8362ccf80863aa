"""`repro.obs` — dependency-free tracing + metrics (DESIGN.md §11).

The measurement seam for the whole stack: the scheduler, engines, block
pool, plan tuner, and train loop bind instruments from the PROCESS
defaults exposed here.  Both default to disabled — a no-op
:class:`~repro.obs.metrics.Registry` and the shared
:data:`~repro.obs.trace.NULL_TRACER` — so instrumentation costs a
no-op method call until something opts in:

    from repro import obs
    obs.enable(trace=True)              # before building engines
    ...
    obs.get_registry().snapshot()       # or obs.export.metrics_report

Instruments are bound at CONSTRUCTION time (an engine built while obs
is disabled keeps its no-op instruments), so enable/`capture` before
building the objects you want measured.  `capture` is the scoped form
used by benches and tests:

    with obs.capture(trace=True) as (reg, tracer):
        eng = PagedEngine(...)
        ...                              # globals restored on exit

Two more seams live here.  :data:`SCOPES` names the model's layers
inside compiled programs: each layer boundary opens one
``jax.named_scope`` of that tuple at its call site, so every device op
carries its layer in its ``op_name`` metadata.  And `enable` installs,
once per process, `jax.monitoring` listeners that count compilations
into whichever registry is current (``compile.*``), and starts the
``attn.*`` counters at 0: the attention call sites count, as they are
traced, which path they took (`models/attention.py`), and the
``precision.*`` counters at 0: the master weights cast to the compute
dtype, as the casts are traced (`models/registry.py::cast_params`).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Tuple

from repro.obs import export, metrics, trace  # noqa: F401 (re-export)
from repro.obs.metrics import (Counter, Gauge, Histogram, NULL_METRIC,
                               Registry, geometric_bounds)
from repro.obs.trace import (NULL_TRACER, NullTracer, Span, Tracer,
                             chrome_trace_events, read_jsonl,
                             request_coverage)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "NULL_METRIC",
    "geometric_bounds",
    "Span", "Tracer", "NullTracer", "NULL_TRACER",
    "chrome_trace_events", "read_jsonl", "request_coverage",
    "get_registry", "get_tracer", "set_registry", "set_tracer",
    "enable", "disable", "capture", "SCOPES", "COMPILE_COUNTERS",
    "ATTN_COUNTERS", "PRECISION_COUNTERS",
    "export", "metrics", "trace",
]

# process defaults: disabled until someone opts in
_registry: Registry = Registry(enabled=False)
_tracer = NULL_TRACER

# The layer scopes of a model's programs (DESIGN.md §11.1), outermost
# first.  Each is a `jax.named_scope` opened at the call site of one
# layer boundary; an op's layer is the innermost of these in its
# `op_name`.  JAX adds `rematted_computation` to ops that a checkpoint
# recomputes in the backward pass.
SCOPES = ("cast", "embed", "blocks", "norm", "attn", "mlp", "moe", "loss",
          "optimizer")

# jax.monitoring events -> the compile.* counters they feed.  The backend
# compile event fires once for each executable JAX builds, compiled by
# XLA or loaded from the persistent cache, and its duration covers either.
_COUNTED = {
    "/jax/core/compile/backend_compile_duration": "compile.backend_compiles",
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
_TIMED = {
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load_s",
}
COMPILE_COUNTERS = tuple(_COUNTED.values()) + tuple(_TIMED.values())
# attention call sites traced through the flash-attention kernels, and
# through the jnp loops (`models/attention.py::blockwise_attention`)
ATTN_COUNTERS = ("attn.kernel_sites", "attn.jnp_sites")
# master-weight leaves cast to the compute dtype, and their stored bytes,
# as the casts are traced (`models/registry.py::cast_params`)
PRECISION_COUNTERS = ("precision.cast_leaves", "precision.cast_bytes")
_listeners_lock = threading.Lock()
_listeners_installed = False


def _on_event(event: str, **_kw) -> None:
    if event in _COUNTED:
        _registry.counter(_COUNTED[event]).inc()


def _on_duration(event: str, secs: float, **_kw) -> None:
    _on_event(event)
    if event in _TIMED:
        _registry.counter(_TIMED[event]).inc(secs)


def _install_compile_listeners() -> None:
    """Register the `jax.monitoring` listeners behind ``compile.*``, once
    per process (`enable` calls this).  They feed the registry current
    when a compile happens, so a disabled registry records nothing."""
    global _listeners_installed
    with _listeners_lock:
        if _listeners_installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listeners_installed = True


def get_registry() -> Registry:
    """The process-default metric registry (no-op unless enabled)."""
    return _registry


def get_tracer():
    """The process-default tracer (NULL_TRACER unless enabled)."""
    return _tracer


def set_registry(registry: Registry) -> Registry:
    """Swap the process default; returns the previous one."""
    global _registry
    old, _registry = _registry, registry
    return old


def set_tracer(tracer) -> object:
    """Swap the process default; returns the previous one."""
    global _tracer
    old, _tracer = _tracer, tracer
    return old


def enable(trace: bool = False,
           clock: Callable[[], float] = time.perf_counter,
           jax_annotate: bool = False) -> Tuple[Registry, object]:
    """Install a fresh enabled registry (and tracer, if ``trace``).

    Returns ``(registry, tracer)`` — the tracer is :data:`NULL_TRACER`
    when tracing stays off.  Call BEFORE constructing the engines /
    schedulers / pools you want instrumented.  The ``compile.*``,
    ``attn.*`` and ``precision.*`` counters start at 0 in the new
    registry."""
    reg = Registry(enabled=True)
    for name in COMPILE_COUNTERS + ATTN_COUNTERS + PRECISION_COUNTERS:
        reg.counter(name)
    tr = Tracer(clock=clock, jax_annotate=jax_annotate) if trace \
        else NULL_TRACER
    set_registry(reg)
    set_tracer(tr)
    _install_compile_listeners()
    return reg, tr


def disable() -> None:
    """Back to the free defaults (no-op registry, null tracer)."""
    set_registry(Registry(enabled=False))
    set_tracer(NULL_TRACER)


@contextlib.contextmanager
def capture(trace: bool = True,
            clock: Callable[[], float] = time.perf_counter,
            jax_annotate: bool = False):
    """Scoped `enable`: yields ``(registry, tracer)``, restores the
    previous process defaults on exit (benches, tests)."""
    old_reg, old_tr = _registry, _tracer
    try:
        yield enable(trace=trace, clock=clock, jax_annotate=jax_annotate)
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)
