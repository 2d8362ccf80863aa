"""f32 master weights under bf16 compute (DESIGN.md §14), on the tiny
qwen3 on the CPU, over the three precision pairs a config can state.

The readings are the benchmark's: three train steps of the train driver
(`bench/drivers/train.py`) from seeded random weights, compared by its
`compare` with the plain f32 reference (`bench/reference.py`).  The
limits are the tiny cell's, from CPU readings of seeds 1-3 and 2**40 + 9:

* f32/f32 reads at most loss 1.1e-7, first gradient 1.4e-6, change
  7.2e-6 (the limits of `bench/tests/test_faults.py`);
* f32/bf16 reads at most loss 4.4e-4, first gradient 3.8e-3, change
  2.0e-3, about what the reference reads with its products' operands
  rounded to bf16 (2.4e-4, 3.4e-3, 1.1e-3 over seeds 1-5);
* the control (the reference's operands rounded to float8) reads at
  least loss 2.2e-3, first gradient 1.6e-2, change 4.5e-3, and fails
  each mixed limit;
* weights stored in bf16 read change 0.975-0.990: the updates round
  away.
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.calibrate import CONTROL  # noqa: E402
from bench.drivers import train  # noqa: E402
from bench.tests.cells import tiny_cell  # noqa: E402
from repro.models.registry import get_arch, init_params  # noqa: E402
from repro.serve import Engine, ServeConfig  # noqa: E402
from repro.train import TrainConfig, build_train_step  # noqa: E402

PAIRS = {"f32": ("float32", "float32"),
         "mixed": ("float32", "bfloat16"),
         "bf16": ("bfloat16", "bfloat16")}
LIMITS = {"f32": {"loss_gap": 1e-5, "first_grad_gap": 1e-4,
                  "change_gap": 5e-4},
          "mixed": {"loss_gap": 1.2e-3, "first_grad_gap": 1e-2,
                    "change_gap": 4e-3}}
SEEDS = (1, 2)


def _arch(pair):
    param, compute = PAIRS[pair]
    arch = get_arch("qwen3-0.6b", reduced=True)
    return dataclasses.replace(arch, cfg=dataclasses.replace(
        arch.cfg, param_dtype=param, compute_dtype=compute))


def _program(pair):
    param, compute = PAIRS[pair]
    cell = tiny_cell("train.s4096")
    cell["model"]["overrides"].update(param_dtype=param,
                                      compute_dtype=compute)
    return train.Program(cell)


def _scans(jaxpr):
    """Every scan equation of a closed jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("pair", list(PAIRS))
def test_step_traces_with_carry_in_compute_and_state_in_param_dtype(pair):
    """The step traces; the layer scan carries the residual stream in the
    compute dtype; the weights keep the stored dtype, and both AdamW
    moments are f32."""
    arch = _arch(pair)
    param, compute = PAIRS[pair]
    tc = TrainConfig(loss_impl="streaming", loss_block_v=128,
                     total_steps=10, warmup_steps=1)
    init_fn, step_fn = build_train_step(arch, tc)
    state = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("tokens", "targets")}
    new_state, _ = jax.eval_shape(step_fn, state, batch)

    def dtypes(tree):
        return {str(x.dtype) for x in jax.tree.leaves(tree)}
    assert dtypes(new_state["params"]) == {param}
    assert dtypes(new_state["opt"]["mu"]) == {"float32"}
    assert dtypes(new_state["opt"]["nu"]) == {"float32"}
    closed = jax.make_jaxpr(step_fn)(state, batch)
    d = arch.cfg.d_model
    carries = [v.aval for eqn in _scans(closed.jaxpr)
               for v in eqn.outvars[:eqn.params["num_carry"]]
               if v.aval.shape == (2, 16, d)]
    assert carries, "no layer scan carries the (B, T, d) residual"
    assert {str(a.dtype) for a in carries} == {compute}


@pytest.mark.parametrize("pair", list(PAIRS))
def test_three_steps_against_the_reference(pair):
    """f32/f32 and f32/bf16 agree with the f32 reference within their
    limits; bf16-stored weights fail `change_gap` by far."""
    prog = _program(pair)
    for seed in SEEDS:
        _, state, feed, loader, got = train.start(prog, seed)
        loader.close()
        nums = train.compare(got, train.reference_readings(prog, seed))
        if pair == "bf16":
            assert nums["change_gap"] > 0.5, (seed, nums)
            assert nums["change_gap"] > 100 * LIMITS["mixed"]["change_gap"]
            continue
        for k, lim in LIMITS[pair].items():
            assert nums[k] <= lim, (seed, k, nums)


def test_fp8_control_fails_the_mixed_limits():
    """The mixed limits are tight enough to catch products a precision
    below bf16: the float8 control fails each of them."""
    prog = _program("mixed")
    ref = train.reference_readings(prog, SEEDS[0])
    nums = train.compare(train.reference_readings(prog, SEEDS[0],
                                                  compute=CONTROL), ref)
    for k, lim in LIMITS["mixed"].items():
        assert nums[k] > lim, (k, nums)


def test_engine_serves_f32_masters_as_their_bf16_cast():
    """Handed f32 weights under bf16 compute, the engine casts them once
    (the embedding table by the rows it looks up) and decodes what the
    same weights stored in bf16 decode."""
    mixed, stored = _arch("mixed"), _arch("bf16")
    params = init_params(mixed, jax.random.PRNGKey(0))
    sc = ServeConfig(batch_size=2, max_len=32, temperature=0.0)
    eng = Engine(mixed, params, sc)
    assert eng.params["lm_head"].dtype == jnp.bfloat16
    assert eng.params["blocks"]["mlp"]["wi"].dtype == jnp.bfloat16
    assert eng.params["embed"]["table"].dtype == jnp.float32
    ref = Engine(stored, jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                      params), sc)
    prompt = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    np.testing.assert_array_equal(eng.generate(prompt, 4),
                                  ref.generate(prompt, 4))


def test_cast_reader_finds_the_cast_scope_by_phase(tmp_path, monkeypatch):
    """The benchmark's `cast.ms_per_step` reader takes an op as the
    cast's by the innermost of its op_name's scopes, and its phase from
    the transpose; with no trace to read it reads nothing."""
    import importlib.util
    path = ROOT / "bench" / "metrics" / "cast.ms_per_step.py"
    spec = importlib.util.spec_from_file_location("cast_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    step = "jit(step_fn)/"
    assert mod.phase(step + "jvp(cast)/convert_element_type") == "forward"
    assert mod.phase(step + "transpose(jvp(cast))/convert_element_type"
                     ) == "backward"
    assert mod.phase(step + "jvp(blocks)/while/body/closed_call/"
                     "checkpoint/attn/dot_general") is None
    assert mod.phase(step + "optimizer/mul") is None
    assert mod.phase(None) is None
    monkeypatch.setattr(mod.sr, "TRACE_ROOT", str(tmp_path))
    assert mod.read({"steps": 1, "trace": {"window_s": 1.0}}) is None
