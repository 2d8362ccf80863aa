"""The scope reduction on a hand-worked excerpt of a traced train window.

One chip, inside the window [1000, 11000] ns: the embedding's gather
[1000, 2000]; the layer scan's `while` [2000, 6000] holding a forward
attention matmul (1000), the scan's own slice (500), an MLP matmul
recomputed under the checkpoint (1500) and an attention backward add
(600), so the `while` keeps 400 of self time; the loss kernels' forward
(1000) and `_dw` (500); a copy with no op_name (500); the optimizer
(1000, and 100 under a `jit(norm)` that is not the `norm` scope); a
final norm (200); a rope table under `attn` cut to 500 by the window's
end; and one op before the window.  The host feeds three times inside
the window (500 + 400 + 200 once clipped) and once after it.
"""

import json
import pathlib

import pytest

from bench import scope_reduce as sr
from bench import trace_reduce as tr

DATA = json.loads(
    (pathlib.Path(__file__).parent / "data" / "trace_scoped.json").read_text())
HOST = [tr.Event(*e) for e in DATA["host"]]
OPS = [sr.DeviceOp(*o) for o in DATA["ops"]]
WINDOW = sr.window_of(HOST)


@pytest.fixture(scope="module")
def got():
    return sr.scopes(OPS, WINDOW)


def _ns(x):
    return pytest.approx(x * 1e-9)


def test_window():
    assert WINDOW == (1000, 11000)


def test_innermost_scope_attribution(got):
    s = got["scopes"]
    assert s["embed"]["forward"] == _ns(1000)          # fusion.0 is outside
    assert s["attn"]["forward"] == _ns(1000 + 500)     # matmul + rope, clipped
    assert s["attn"]["backward"] == _ns(600)
    assert s["loss"] == {"forward": _ns(1000), "recompute": 0.0,
                         "backward": _ns(500)}
    assert s["optimizer"]["forward"] == _ns(1000 + 100)
    assert s["norm"]["forward"] == _ns(200)


def test_rematted_computation_kept_apart(got):
    s = got["scopes"]
    assert s["mlp"] == {"forward": 0.0, "recompute": _ns(1500),
                        "backward": 0.0}
    assert s["attn"]["recompute"] == 0.0
    assert got["remat_s"] == _ns(1500)


def test_remat_counts_unscoped_recompute():
    # a program without the layer scopes still reads its recompute
    bare = [sr.DeviceOp("/device:TPU:0", 2000, 1500, "fusion.5",
                        "jit(step_fn)/transpose(jvp())/while/body/"
                        "checkpoint/rematted_computation/dot_general"),
            sr.DeviceOp("/device:TPU:0", 3500, 500, "fusion.6",
                        "jit(step_fn)/jvp()/while/body/dot_general")]
    got = sr.scopes(bare, WINDOW)
    assert got["scopes"] == {}
    assert got["remat_s"] == _ns(1500)
    assert got["unscoped_s"] == _ns(2000)


def test_unscoped_ops_under_blocks(got):
    # the scan's slice and the while's own time land on `blocks`
    assert got["scopes"]["blocks"] == {"forward": _ns(500 + 400),
                                       "recompute": 0.0, "backward": 0.0}
    assert got["unscoped_s"] == _ns(500)
    assert got["unscoped_ops"] == [["copy.8", _ns(500)]]


def test_scopes_and_rest_sum_to_busy(got):
    scoped = sum(sum(p.values()) for p in got["scopes"].values())
    assert scoped + got["unscoped_s"] == pytest.approx(got["busy_s"])
    events = HOST + [tr.Event(o.plane, tr.OPS_LINE, o.op, o.start_ns,
                              o.dur_ns) for o in OPS]
    assert got["busy_s"] == pytest.approx(tr.reduce(events)["busy_s"])


@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/closed_call/"
     "checkpoint/attn/add_any", ("attn", "backward")),
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/closed_call/"
     "checkpoint/rematted_computation/attn/dot_general",
     ("attn", "recompute")),
    ("jit(step_fn)/transpose(jvp(embed))/scatter-add", ("embed", "backward")),
    ("jit(step_fn)/jvp(blocks)/while/body/closed_call/attn/reshape;"
     "mlp/transpose", ("attn", "forward")),
    ("jit(step_fn)/optimizer/jit(norm)/sqrt", ("optimizer", "forward")),
    ("jit(step_fn)/add", (None, "forward")),
    (None, (None, "forward")),
])
def test_scope_of(op_name, want):
    assert sr.scope_of(op_name) == want


def test_hlo_op_names():
    text = "\n".join([
        "ENTRY %main.9 (p: f32[4]) -> f32[4] {",
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(step_fn)/optimizer/sub" '
        'source_file="x.py" source_line=3}',
        "  %copy.4 = f32[4]{0} copy(%fusion.3)",
        '  ROOT %tuple.5 = (f32[4]) tuple(%copy.4), '
        'metadata={op_name="jit(step_fn)"}',
        "}"])
    assert sr.hlo_op_names(text) == {
        "fusion.3": "jit(step_fn)/optimizer/sub", "tuple.5": "jit(step_fn)"}


def test_train_feed_spans_clipped_to_the_window():
    got = sr.spans(HOST, WINDOW)
    assert got["train.feed"] == {"s": _ns(500 + 400 + 200), "n": 3}
    assert got["train.dispatch"] == {"s": _ns(200), "n": 1}
    assert got["train.step"] == {"s": _ns(5900), "n": 1}
    assert "TpuClient::DefragmentMemory" not in got


def test_window_compile_deltas():
    c = DATA["counters"]
    setup = sr.deltas(c["setup_start"], c["window_start"])
    window = sr.deltas(c["window_start"], c["window_end"])
    assert setup["compile.backend_compiles"] == 12
    assert setup["compile.backend_s"] == pytest.approx(80.5)
    assert window == dict.fromkeys(c["window_end"], 0)


def test_scopes_are_the_programs():
    from repro import obs
    assert sr.SCOPES == obs.SCOPES


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 3500000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(%a)" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step_fn(8815)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 7000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
"""


def test_load_finds_op_names_by_module_and_instruction():
    # as on the v5e: an op's module is the `XLA Modules` event over it
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(XSPACE)
    names = {"jit_step_fn": {"fusion.1": "jit(step_fn)/optimizer/sub",
                             "copy.2": "jit(step_fn)/add"}}
    ops, host = sr.load(pd, names)
    assert ops == [
        sr.DeviceOp("/device:TPU:0", 1000, 2000, "fusion.1",
                    "jit(step_fn)/optimizer/sub"),
        sr.DeviceOp("/device:TPU:0", 3000, 1000, "copy.2",
                    "jit(step_fn)/add"),
        sr.DeviceOp("/device:TPU:0", 6000, 500, "fusion.1", None)]
    assert host == [tr.Event("/host:CPU", "python", "bench.window", 0, 7000)]


def test_live_op_names_hold_a_jitted_function_s_scopes():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x) * 2.0

    jf = jax.jit(f)
    jf(jnp.ones(8)).block_until_ready()
    names = sr.live_op_names()
    mine = [n for mod, ops in names.items() if mod.startswith("jit_f")
            for n in ops.values()]
    assert any(sr.scope_of(n)[0] == "mlp" for n in mine), names.keys()
