"""Launcher CLIs + dry-run helpers (single-device portions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.models.registry import get_arch


def test_mesh_module_is_pure():
    """Importing launch.mesh must not touch jax device state."""
    import importlib
    import repro.launch.mesh as M
    importlib.reload(M)          # no exceptions, no device init required
    assert callable(M.make_production_mesh)


def test_train_cli_end_to_end(tmp_path):
    from repro.launch.train import main
    state, history = main([
        "--arch", "qwen3-0.6b", "--reduced", "--steps", "6",
        "--global-batch", "4", "--seq-len", "32",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
        "--log-every", "2"])
    assert int(jax.device_get(state["step"])) == 6
    assert history and np.isfinite(history[-1][1]["loss"])
    # resume picks up the checkpoint
    state2, _ = main([
        "--arch", "qwen3-0.6b", "--reduced", "--steps", "8",
        "--global-batch", "4", "--seq-len", "32",
        "--ckpt-dir", str(tmp_path)])
    assert int(jax.device_get(state2["step"])) == 8


def test_train_cli_reports_the_weight_cast(tmp_path, monkeypatch):
    """f32 masters under bf16 compute (the full qwen3-0.6b config's
    precision) train through the CLI, and `--metrics-json` reports the
    leaves cast and their bytes."""
    import dataclasses
    import json
    from repro.launch import train
    full = get_arch("qwen3-0.6b")
    assert (full.cfg.param_dtype, full.cfg.compute_dtype) == (
        "float32", "bfloat16")

    def tiny_mixed(arch_id, reduced=False):
        arch = get_arch(arch_id, reduced=reduced)
        return dataclasses.replace(arch, cfg=dataclasses.replace(
            arch.cfg, param_dtype=full.cfg.param_dtype,
            compute_dtype=full.cfg.compute_dtype))
    monkeypatch.setattr(train, "get_arch", tiny_mixed)
    out = tmp_path / "metrics.json"
    state, history = train.main([
        "--arch", "qwen3-0.6b", "--reduced", "--steps", "2",
        "--global-batch", "2", "--seq-len", "16", "--log-every", "1",
        "--metrics-json", str(out)])
    assert np.isfinite(history[-1][1]["loss"])
    assert state["params"]["lm_head"].dtype == jnp.float32
    got = json.loads(out.read_text())["metrics"]
    assert got["precision.cast_leaves"]["value"] > 0
    assert got["precision.cast_bytes"]["value"] > 0


def test_train_cli_sharded_loss_needs_a_mesh():
    """--loss-impl sharded without --devices used to train with the
    streaming loss instead; it must refuse."""
    from repro.launch.train import main
    with pytest.raises(ValueError, match="mesh"):
        main(["--arch", "qwen3-0.6b", "--reduced", "--steps", "1",
              "--global-batch", "2", "--seq-len", "16",
              "--loss-impl", "sharded"])


def test_compile_cache_dir(monkeypatch):
    """The cache stays where JAX_COMPILATION_CACHE_DIR says; otherwise it
    is one fixed directory of the checkout."""
    from repro.launch import compile_cache
    seen = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_compile_cache()
    assert path == compile_cache.use_compile_cache()
    assert path.endswith(".jax_cache")
    assert seen == [("jax_compilation_cache_dir", path)] * 2


def test_serve_cli(capsys):
    from repro.launch.serve import main
    out = main(["--arch", "qwen3-0.6b", "--reduced", "--batch", "2",
                "--prompt-len", "6", "--max-new", "3"])
    assert out.shape == (2, 3)


def test_serve_cli_paged(capsys):
    from repro.launch.serve import main
    out = main(["--arch", "qwen3-0.6b", "--reduced", "--batch", "2",
                "--prompt-len", "6", "--max-new", "3", "--paged",
                "--block-size", "8", "--paged-impl", "jax"])
    assert out.shape == (2, 3)
    assert "paged:" in capsys.readouterr().out


def test_dryrun_cell_enumeration():
    from repro.launch.dryrun import iter_cells
    cells = list(iter_cells())
    assert len(cells) == 10 * 4 * 2
    singles = [c for c in cells if not c[2]]
    assert len(singles) == 40
    # supported-cell count matches the assignment's 32 (10*4 - 8 skips)
    supported = sum(get_arch(a).supports(s) for a, s, m in singles)
    assert supported == 32


def test_analytic_flops_moe_discount():
    from repro.launch.dryrun import _analytic_flops_per_device
    arch = get_arch("qwen3-moe-235b-a22b")
    params_struct = jax.eval_shape(
        lambda r: __import__("repro.models.registry",
                             fromlist=["init_params"]).init_params(arch, r),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    ana = _analytic_flops_per_device(arch, "train_4k", params_struct, 256)
    assert ana["n_active_params"] < 0.2 * ana["n_params"]   # top8 of 128
    assert ana["model_flops"] == 6.0 * ana["n_active_params"] * \
        SHAPES["train_4k"].global_batch * SHAPES["train_4k"].seq_len


def test_report_tables_generate():
    from repro.analysis import report
    recs = report.load()
    if not recs:
        pytest.skip("no dryrun artifacts present")
    t = report.dryrun_table(recs)
    assert "| arch | shape |" in t
    r = report.roofline_table(recs)
    assert "dominant" in r
    m = report.multipod_table(recs)
    assert "2-pod" in m
