"""repro.obs: fake-clock span semantics, histogram quantiles vs numpy,
the disabled no-op identity (same scheduler tokens, zero instruments),
JSONL / Chrome trace round-trips, the export formats, the layer scopes
in a compiled train step, the train loop's spans and the compile
counters."""

import json
import re

import numpy as np
import pytest

import repro.serve.scheduler as sched_mod
from repro import obs
from repro.distributed.fault import StragglerMonitor
from repro.obs.metrics import NULL_METRIC
from repro.train import train_loop
from tests.test_scheduler import FakeClock, FakeEngine


# -- tracer ---------------------------------------------------------------

def make_ticker(step=1.0):
    """A clock that advances `step` every call (deterministic spans)."""
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]
    return clock


def test_span_nesting_and_completion_order():
    tr = obs.Tracer(clock=make_ticker())
    with tr.span("outer", cat="t", a=1):
        with tr.span("inner", cat="t"):
            pass
    # inner completes first; depth records the nesting
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    inner, outer = tr.spans
    assert inner.depth == 1 and outer.depth == 0
    # ticker: outer.start=1, inner.start=2, inner.end=3, outer.end=4
    assert (outer.start, inner.start, inner.end, outer.end) == \
        (1.0, 2.0, 3.0, 4.0)
    assert outer.args == {"a": 1}
    assert inner.duration == 1.0


def test_add_span_and_step_span():
    tr = obs.Tracer(clock=make_ticker())
    tr.add_span("req.queue", 0.5, 1.5, cat="request", rid=3)
    with tr.step_span("train.step", 7):
        pass
    assert tr.spans[0].args == {"rid": 3}
    assert tr.spans[0].duration == 1.0
    assert tr.spans[1].cat == "step"
    assert tr.spans[1].args == {"step": 7}


def test_null_tracer_is_free_and_shared():
    ctx1 = obs.NULL_TRACER.span("anything", x=1)
    ctx2 = obs.NULL_TRACER.step_span("s", 0)
    assert ctx1 is ctx2                    # one shared no-op ctx manager
    with ctx1:
        pass
    obs.NULL_TRACER.add_span("n", 0.0, 1.0)
    assert obs.NULL_TRACER.spans == ()


def test_jsonl_round_trip(tmp_path):
    tr = obs.Tracer(clock=make_ticker())
    with tr.span("a", cat="c", k="v"):
        pass
    tr.add_span("b", 1.0, 2.5, rid=1)
    p = str(tmp_path / "t.jsonl")
    assert tr.export_jsonl(p) == 2
    back = obs.read_jsonl(p)
    assert back == tr.spans                # Span.__eq__ round-trip exact


def test_chrome_trace_events(tmp_path):
    tr = obs.Tracer(clock=make_ticker())
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    p = str(tmp_path / "t.json")
    assert tr.export_chrome(p) == 2
    with open(p) as f:
        doc = json.load(f)
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert evs["outer"]["ph"] == "X"
    assert evs["inner"]["tid"] == 1        # one track per depth
    assert evs["outer"]["tid"] == 0
    # microsecond complete events: inner lies inside outer
    assert evs["outer"]["ts"] < evs["inner"]["ts"]
    assert evs["inner"]["dur"] < evs["outer"]["dur"]


def test_request_coverage_math():
    tr = obs.Tracer()
    tr.add_span("req", 0.0, 10.0, rid=1)
    tr.add_span("req.queue", 0.0, 2.0, cat="request", rid=1)
    tr.add_span("req.prefill", 2.0, 3.0, cat="request", rid=1)
    tr.add_span("req.decode", 3.0, 9.0, cat="request", rid=1)
    cov = obs.request_coverage(tr.spans)
    assert cov == {1: pytest.approx(0.9)}


# -- histogram ------------------------------------------------------------

def test_histogram_exact_quantiles_match_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-4.0, sigma=1.5, size=1000)
    h = obs.Histogram("x")
    for v in xs:
        h.observe(v)
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(
            float(np.percentile(xs, 100 * q)), rel=1e-12)
    assert h.count == 1000
    assert h.mean == pytest.approx(float(xs.mean()))
    assert h.min == xs.min() and h.max == xs.max()


def test_histogram_bucket_estimate_bounded_error():
    rng = np.random.default_rng(1)
    xs = rng.lognormal(mean=-4.0, sigma=1.5, size=5000)
    h = obs.Histogram("x", exact_cap=100)     # force stream mode
    for v in xs:
        h.observe(v)
    assert h._exact is None                   # reservoir dropped
    for q in (0.5, 0.95, 0.99):
        exact = float(np.percentile(xs, 100 * q))
        est = h.quantile(q)
        # geometric buckets at 20/decade: ~12% relative bound in-range
        assert abs(est - exact) / exact < 0.15, (q, est, exact)
    assert h.min <= h.quantile(0.0) <= h.quantile(1.0) <= h.max


def test_histogram_empty_and_validation():
    h = obs.Histogram("x")
    assert h.quantile(0.5) == 0.0
    assert h.snapshot() == {"count": 0, "sum": 0.0}
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        obs.Histogram("y", bounds=[2.0, 1.0])
    with pytest.raises(ValueError):
        obs.geometric_bounds(lo=-1.0)


# -- registry -------------------------------------------------------------

def test_disabled_registry_is_noop_identity():
    reg = obs.Registry(enabled=False)
    c = reg.counter("a.b_total")
    g = reg.gauge("a.level")
    h = reg.histogram("a.t_s")
    assert c is NULL_METRIC and g is NULL_METRIC and h is NULL_METRIC
    c.inc()
    g.set(3)
    h.observe(0.5)
    assert len(reg) == 0                   # nothing was ever allocated
    assert reg.snapshot() == {}


def test_enabled_registry_shares_and_type_checks():
    reg = obs.Registry()
    c1 = reg.counter("x_total", "help text")
    c2 = reg.counter("x_total")
    assert c1 is c2                        # one series per name
    c1.inc(2)
    c2.inc()
    assert reg.snapshot()["x_total"] == {"kind": "counter", "value": 3.0}
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    with pytest.raises(TypeError):
        reg.histogram("x_total")


def test_capture_restores_process_defaults():
    before_reg, before_tr = obs.get_registry(), obs.get_tracer()
    with obs.capture(trace=True) as (reg, tracer):
        assert obs.get_registry() is reg and reg.enabled
        assert obs.get_tracer() is tracer and tracer.enabled
    assert obs.get_registry() is before_reg
    assert obs.get_tracer() is before_tr


# -- scheduler integration ------------------------------------------------

def _run_sched(n_req=5, batch_size=2, max_new=3):
    eng = FakeEngine(batch_size=batch_size)
    sched = sched_mod.ContinuousScheduler(eng, max_new_tokens=max_new)
    rids = [sched.submit(np.arange(2 + i)) for i in range(n_req)]
    res = sched.run()
    return {r: list(res[r]) for r in rids}


def test_scheduler_tokens_identical_disabled_vs_enabled(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(sched_mod, "time", clock)
    obs.disable()
    base = _run_sched()
    with obs.capture(trace=True):
        instrumented = _run_sched()
    assert instrumented == base            # observation changes nothing


def test_scheduler_spans_cover_requests(monkeypatch):
    clock = FakeClock()

    def tick():
        clock.t += 0.25
        return clock.t
    monkeypatch.setattr(clock, "perf_counter", tick)
    monkeypatch.setattr(sched_mod, "time", clock)

    with obs.capture(trace=True) as (reg, tracer):
        eng = FakeEngine(batch_size=2)
        sched = sched_mod.ContinuousScheduler(eng, max_new_tokens=3)
        rids = [sched.submit(np.arange(3)) for _ in range(4)]
        sched.run()
        cov = obs.request_coverage(tracer.spans)
        assert sorted(cov) == sorted(rids)
        for rid, frac in cov.items():
            assert frac == pytest.approx(1.0), (rid, frac)
        # lifecycle phases abut: queue end == prefill start, etc.
        by_req = {}
        for s in tracer.spans:
            if s.cat == "request":
                by_req.setdefault(s.args["rid"], {})[s.name] = s
        for rid, phases in by_req.items():
            assert set(phases) == {"req.queue", "req.prefill",
                                   "req.decode"}
            assert phases["req.queue"].end == phases["req.prefill"].start
            assert phases["req.prefill"].end == \
                phases["req.decode"].start
        # serve metrics recorded real populations
        snap = reg.snapshot()
        assert snap["serve.requests_finished_total"]["value"] == 4
        assert snap["serve.ttft_s"]["count"] == 4
        assert snap["serve.ttft_s"]["p95"] >= snap["serve.ttft_s"]["p50"]


def test_scheduler_stats_quantiles(monkeypatch):
    clock = FakeClock()

    def tick():
        clock.t += 0.125
        return clock.t
    monkeypatch.setattr(clock, "perf_counter", tick)
    monkeypatch.setattr(sched_mod, "time", clock)
    eng = FakeEngine(batch_size=2)
    sched = sched_mod.ContinuousScheduler(eng, max_new_tokens=4)
    for i in range(6):
        sched.submit(np.arange(2 + i))
    sched.run()
    st = sched.stats()
    for key in ("ttft_s", "latency_s", "queue_wait_s", "tpot_s"):
        summ = st[key]
        # pre-existing keys survive; quantile keys are new
        assert set(summ) == {"mean", "max", "p50", "p95", "p99"}
        assert summ["p50"] <= summ["p95"] <= summ["p99"] <= summ["max"]
    vals = [v["ttft_s"] for v in st["per_request"].values()]
    assert st["ttft_s"]["p50"] == pytest.approx(
        float(np.percentile(vals, 50)), abs=1e-6)


# -- export ---------------------------------------------------------------

def test_metrics_report_and_dump_json(tmp_path, capsys):
    reg = obs.Registry()
    reg.counter("a_total").inc(2)
    reg.histogram("b_s").observe(0.5)
    rep = obs.export.metrics_report(reg, extra={"mode": "test"})
    assert rep["schema"] == "repro.obs/1"
    assert rep["mode"] == "test"
    assert rep["metrics"]["a_total"]["value"] == 2.0
    p = str(tmp_path / "m.json")
    obs.export.dump_json(rep, p)
    with open(p) as f:
        assert json.load(f) == rep
    obs.export.dump_json({"x": 1}, "-")
    assert '"x": 1' in capsys.readouterr().out


def test_write_trace_formats(tmp_path):
    with obs.capture(trace=True) as (_, tracer):
        with tracer.span("s"):
            pass
    assert obs.export.write_trace(tracer, str(tmp_path / "a.json")) == 1
    assert obs.export.write_trace(tracer, str(tmp_path / "a.jsonl"),
                                  fmt="jsonl") == 1
    with pytest.raises(ValueError):
        obs.export.write_trace(tracer, str(tmp_path / "x"), fmt="nope")


def test_kvpool_fork_updates_counter_and_gauge():
    """Regression: `BlockPool.fork` used to skip `_track()` and the
    forks counter — a fork-heavy beam workload showed a stale
    `blocks_in_use` gauge and zero `forks_total`.  Every fork must tick
    the counter, and the gauge must equal `used_blocks` after every
    mutation (duplicate-id chains included)."""
    from repro.serve.kvpool import BlockPool, PagedConfig

    with obs.capture() as (reg, _):
        pool = BlockPool(PagedConfig(block_size=4, n_blocks=8,
                                     max_blocks_per_slot=8))
        chain = pool.alloc(3)
        for _ in range(4):
            pool.fork(chain)
        pool.fork([chain[0], chain[0]])          # duplicate-id chain
        snap = reg.snapshot()
        assert snap["kvpool.forks_total"]["value"] == 5
        assert snap["kvpool.blocks_in_use"]["value"] == pool.used_blocks
        # unwind every reference; the gauge follows back down to zero
        for _ in range(4):
            pool.free(chain)
        pool.free([chain[0], chain[0]])
        pool.free(chain)
        snap = reg.snapshot()
        assert pool.used_blocks == 0
        assert snap["kvpool.blocks_in_use"]["value"] == 0
        assert snap["kvpool.free_blocks"]["value"] == pool.free_blocks


def test_beam_group_metrics_and_fork_instrumentation():
    """A fork-heavy width-3 beam on the real paged engine: the modes
    counters and the kvpool fork instrumentation record the run."""
    import jax
    from repro.models.registry import get_arch, init_params
    from repro.serve import PagedEngine, ServeConfig

    arch = get_arch("qwen3-0.6b", reduced=True)
    params = init_params(arch, jax.random.PRNGKey(0))
    with obs.capture() as (reg, _):
        eng = PagedEngine(arch, params, ServeConfig(
            batch_size=4, max_len=64, paged=True, block_size=8))
        sched = sched_mod.ContinuousScheduler(eng, max_new_tokens=4)
        rid = sched.submit_beam(
            np.arange(1, 18, dtype=np.int32), n_beams=3)
        sched.run()
        snap = reg.snapshot()
        assert snap["serve.beam_groups_total"]["value"] == 1
        assert snap["serve.beam_forks_total"]["value"] == \
            sched.group_forks > 0
        assert snap["serve.beam_pruned_total"]["value"] == \
            sched.group_pruned
        assert snap["kvpool.forks_total"]["value"] >= sched.group_forks
        assert snap["kvpool.blocks_in_use"]["value"] == \
            eng.pool.used_blocks
        assert len(sched.hypotheses[rid]) == 3


# -- layer scopes, loop spans, compile counters ----------------------------

def _scopes_of(op_name):
    """The `obs.SCOPES` named by an op_name's components, transform
    wrappers such as ``transpose(jvp(attn))`` unwrapped."""
    return [s for s in obs.SCOPES
            if re.search(rf"(^|[/(]){s}([/)]|$)", op_name)]


def test_every_matmul_and_call_in_the_train_step_has_a_scope():
    import jax
    import jax.numpy as jnp
    from repro.models.registry import get_arch
    from repro.train.step import TrainConfig, build_train_step
    arch = get_arch("qwen3-0.6b", reduced=True)
    tc = TrainConfig(loss_impl="pallas", loss_block_v=128, total_steps=10,
                     warmup_steps=1)
    init_fn, step_fn = build_train_step(arch, tc)
    state = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("tokens", "targets")}
    text = jax.jit(step_fn, donate_argnums=(0,)).lower(
        state, batch).compile().as_text()
    ops = re.compile(r"= \S+ (dot|convolution|custom-call)\(")
    seen, bare = 0, []
    for line in text.splitlines():
        if not ops.search(line):
            continue
        seen += 1
        m = re.search(r'op_name="([^"]*)"', line)
        if m is None or not _scopes_of(m.group(1)):
            bare.append(line.strip()[:160])
    assert seen > 10
    assert not bare, bare
    # forward, backward and recompute of a layer each keep its scope
    for pat in (r'jvp\(blocks\)/[^"]*/attn/',
                r'transpose\(jvp\(blocks\)\)[^"]*/rematted_computation/mlp/',
                r'/optimizer/'):
        assert re.search(pat, text), pat


@pytest.mark.parametrize("compute", ["bfloat16", "float32"])
def test_the_weight_cast_has_its_scope_and_counters(compute):
    """Under bf16 compute the f32 masters are cast once, under the `cast`
    scope, and the counters read the leaves cast (every leaf but the
    embedding table) and their f32 bytes; with compute in f32 no op
    carries the scope and the counters read 0."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.models.registry import get_arch
    from repro.train.step import TrainConfig, build_train_step
    arch = get_arch("qwen3-0.6b", reduced=True)
    arch = dataclasses.replace(arch, cfg=dataclasses.replace(
        arch.cfg, param_dtype="float32", compute_dtype=compute))
    tc = TrainConfig(loss_impl="streaming", loss_block_v=128,
                     total_steps=10, warmup_steps=1)
    init_fn, step_fn = build_train_step(arch, tc)
    state = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("tokens", "targets")}
    with obs.capture() as (reg, _):
        text = jax.jit(step_fn).lower(state, batch).as_text(
            debug_info=True)
        snap = reg.snapshot()
    cast = [x for k, x in jax.tree_util.tree_flatten_with_path(
        state["params"])[0] if k[0].key != "embed"]
    mixed = compute == "bfloat16"
    assert snap["precision.cast_leaves"]["value"] == (
        len(cast) if mixed else 0)
    assert snap["precision.cast_bytes"]["value"] == (
        sum(4 * x.size for x in cast) if mixed else 0)
    assert bool(re.search(r'jvp\(cast\)/convert_element_type', text)) \
        == mixed
    assert bool(re.search(r'transpose\(jvp\(cast\)\)', text)) == mixed


class _Stay:
    """A preemption stand-in that never asks to stop."""

    should_stop = False

    def install(self):
        return self


def _run_loop(steps=2):
    import jax.numpy as jnp

    def step_fn(state, batch):
        return {"step": state["step"] + 1}, {"loss": jnp.float32(1.0)}

    data = [{"tokens": np.zeros((2, 4), np.int32)}] * steps
    return train_loop(state={"step": jnp.int32(0)}, step_fn=step_fn,
                      data=data, num_steps=steps, log_every=100,
                      preemption=_Stay(), straggler=StragglerMonitor())


def test_train_loop_spans_nest_under_the_step():
    with obs.capture(trace=True, clock=make_ticker()) as (_, tr):
        _run_loop(steps=2)
    got = [(s.name, s.start, s.end, s.depth) for s in tr.spans]
    # ticker: each clock read advances 1; step 0 is also the logged step
    assert got[:5] == [("train.feed", 2.0, 3.0, 1),
                       ("train.dispatch", 4.0, 5.0, 1),
                       ("train.wait", 6.0, 7.0, 1),
                       ("train.step", 1.0, 8.0, 0),
                       ("train.log", 9.0, 10.0, 0)]
    assert [s.name for s in tr.spans[5:]] == [
        "train.feed", "train.dispatch", "train.wait", "train.step"]
    assert tr.spans[-1].args == {"step": 1}


def test_train_loop_records_nothing_under_the_null_tracer():
    obs.disable()
    assert obs.get_tracer() is obs.NULL_TRACER
    _run_loop(steps=2)
    assert obs.NULL_TRACER.spans == ()
    assert len(obs.get_registry()) == 0


def test_a_fresh_jit_counts_one_backend_compile():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    with obs.capture(trace=False) as (reg, _):
        assert all(reg.get(n).value == 0 for n in obs.COMPILE_COUNTERS)
        f(x).block_until_ready()
        assert reg.get("compile.backend_compiles").value == 1
        assert reg.get("compile.backend_s").value > 0
        f(x).block_until_ready()
        assert reg.get("compile.backend_compiles").value == 1
    # a disabled registry is fed nothing
    obs.disable()
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    assert len(obs.get_registry()) == 0


def test_enable_twice_registers_the_listeners_once():
    from jax._src import monitoring as jm
    obs.enable()
    obs.enable(trace=True)
    obs.disable()
    assert jm._event_listeners.count(obs._on_event) == 1
    assert jm._event_duration_secs_listeners.count(obs._on_duration) == 1


@pytest.mark.parametrize("impl,sites", [("pallas", 1), ("streaming", 0)])
def test_the_fused_loss_counts_its_backward_kernels(tmp_path, impl, sites):
    """Each fused-loss backward kernel the train step traces counts once
    in ``fused_ce.bwd_sites``: one for the Pallas loss, none for the
    streaming loss, which has no kernel; `launch/train.py
    --metrics-json` reports the counter."""
    import json
    from repro.launch import train
    out = tmp_path / "metrics.json"
    train.main(["--arch", "qwen3-0.6b", "--reduced", "--steps", "1",
                "--global-batch", "2", "--seq-len", "16",
                "--loss-impl", impl, "--metrics-json", str(out)])
    got = json.loads(out.read_text())["metrics"]
    assert got["fused_ce.bwd_sites"]["value"] == sites
