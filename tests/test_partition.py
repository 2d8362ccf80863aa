"""serve/partition.cache_specs: the full family x layout grid.

Every serve-cache leaf must get a PartitionSpec of matching rank —
``len(spec) <= leaf.ndim`` with trailing dims implicitly unsharded
(`repair_spec` trims trailing Nones; anything LONGER is a GSPMD error
at scale) — k/v head dims must land on the model axis, and paged pool
leaves must never shard their (shared, slot-less) pool dim over the
batch axes."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_local_mesh
from repro.models.registry import empty_serve_caches, get_arch, init_params
from repro.serve.kvpool import paged_config
from repro.serve.partition import batch_specs, cache_specs
from repro.sharding.rules import AxisRules

FAMILIES = ["qwen3-0.6b", "recurrentgemma-9b", "xlstm-125m",
            "seamless-m4t-medium"]


def _arch(arch_id, scanned):
    arch = get_arch(arch_id, reduced=True)
    if not scanned:
        arch = dataclasses.replace(
            arch, cfg=dataclasses.replace(arch.cfg, scan_layers=False))
    return arch


def _rules():
    return AxisRules(mesh=make_local_mesh(1, 1))


def _at(spec, i):
    """PartitionSpec entry i (trailing trimmed Nones included)."""
    return spec[i] if i < len(spec) else None


def _leaves_with_names(tree):
    from jax.sharding import PartitionSpec

    out = []

    def walk(path, sub):
        if isinstance(sub, dict):
            # sorted: mirror jax pytree key order so a tree walk and a
            # tree_map-built specs walk pair up leaf-for-leaf
            for k in sorted(sub):
                walk(path + (k,), sub[k])
        elif isinstance(sub, (list, tuple)) \
                and not isinstance(sub, PartitionSpec):
            for i, v in enumerate(sub):
                walk(path + (i,), v)
        else:
            name = next((p for p in reversed(path) if isinstance(p, str)),
                        "")
            out.append((name, sub))

    walk((), tree)
    return out


@pytest.mark.parametrize("scanned", [True, False])
@pytest.mark.parametrize("arch_id", FAMILIES)
def test_cache_specs_rank_and_kv_sharding(arch_id, scanned):
    arch = _arch(arch_id, scanned)
    params = init_params(arch, jax.random.PRNGKey(0))
    tree = empty_serve_caches(arch, params, 2, 32, enc_len=8,
                              dtype=jnp.bfloat16)
    rules = _rules()
    specs = cache_specs(arch, tree, rules)
    flat_t, td = jax.tree.flatten(tree)
    flat_s = td.flatten_up_to(specs)
    assert len(flat_t) == len(flat_s)
    for leaf, spec in zip(flat_t, flat_s):
        assert len(spec) <= leaf.ndim, (leaf.shape, spec)
    lead = 1 if getattr(arch.cfg, "scan_layers", True) else 0
    kv = [(name, leaf, spec) for (name, leaf), (_, spec) in
          zip(_leaves_with_names(tree), _leaves_with_names(specs))
          if name in ("k", "v") and leaf.ndim >= lead + 4]
    assert (len(kv) > 0) == (arch.family != "xlstm")
    for name, leaf, spec in kv:
        assert "model" in jax.tree.leaves([_at(spec, lead + 2)]), (
            f"{name} head dim not on the model axis: {spec}")


@pytest.mark.parametrize("scanned", [True, False])
@pytest.mark.parametrize("arch_id", ["qwen3-0.6b", "seamless-m4t-medium"])
def test_cache_specs_paged_pools(arch_id, scanned):
    """Paged pools: kv heads on 'model', pool/block dims unsharded, NO
    batch axis anywhere; tables shard the slot dim like other leaves."""
    arch = _arch(arch_id, scanned)
    params = init_params(arch, jax.random.PRNGKey(0))
    pc = paged_config(block_size=8, max_len=32, batch_size=2)
    tree = empty_serve_caches(arch, params, 2, 32, enc_len=8,
                              dtype=jnp.bfloat16, paged=pc)
    rules = _rules()
    specs = cache_specs(arch, tree, rules)
    lead = 1 if getattr(arch.cfg, "scan_layers", True) else 0
    named_t = _leaves_with_names(tree)
    named_s = _leaves_with_names(specs)
    assert any(n in ("kp", "vp") for n, _ in named_t)
    batch_axes = {"data", "pod"}
    for (name, leaf), (_, spec) in zip(named_t, named_s):
        assert len(spec) <= leaf.ndim
        if name in ("kp", "vp"):
            assert "model" in jax.tree.leaves([_at(spec, lead + 2)])
            flat = set(jax.tree.leaves([list(spec)]))
            assert not (flat & batch_axes), (
                f"pool leaf {name} sharded over batch: {spec}")
        if name == "table":
            assert "data" in jax.tree.leaves([_at(spec, lead)])
            assert all(s is None for i, s in enumerate(spec)
                       if i != lead)


def test_batch_specs_rank():
    arch = get_arch("qwen3-0.6b", reduced=True)
    rules = _rules()
    tree = {"tokens": jnp.zeros((4, 16), jnp.int32),
            "frontend_embeds": jnp.zeros((4, 8, 16), jnp.bfloat16)}
    specs = batch_specs(arch, tree, rules)
    flat_t, td = jax.tree.flatten(tree)
    for leaf, spec in zip(flat_t, td.flatten_up_to(specs)):
        assert len(spec) <= leaf.ndim
        assert "data" in jax.tree.leaves([_at(spec, 0)])
