"""Quantized serving: int8 KV paging + quantized lm_head (DESIGN.md §10).

Three claims, each with a deterministic check:

  * **identity** — quantized-paged greedy decode is token-IDENTICAL to
    the quantized slab engine (both decode impls; the pallas kernel's
    in-register dequant reproduces `_decode_quantized`'s slab math bit
    for bit), and matches the bf16 paged engine's tokens at or above a
    calibrated per-token rate (quantization noise may flip near-ties,
    never the bulk).
  * **memory** — live pool bytes per admitted request are <= 0.55x the
    bf16 paged engine's, WITH the per-block scale pools counted against
    the quantized side (int8 payload halves the bytes; scales claw back
    4/head_dim of it).
  * **HLO hygiene** — the compiled quantized decode step materializes
    neither a logits tensor (`assert_logits_free`) nor a full-size
    dequantized copy of the int8 K/V pools, the gathered cache, or the
    quantized lm_head (`assert_no_wide_dequant`): dequantization only
    ever happens one VMEM tile at a time inside the kernels.

The reduced qwen3 arch is rebuilt with ``head_dim=64`` here: the memory
claim is about the scale overhead ratio ``(hd + 4) / (2 * hd)``, which
the test-tier ``head_dim=16`` (0.625) can never bring under 0.55 while
the serving-class 64 (0.53) can — the bench measures the regime the
paper serves in, not the unit-test miniature.

Run:  PYTHONPATH=src python -m benchmarks.bench_quant [--smoke]
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from benchmarks.bench_serve import make_workload
from repro.analysis.hlo import assert_logits_free, assert_no_wide_dequant
from repro.models.registry import get_arch, init_params
from repro.serve import (ContinuousScheduler, Engine, PagedEngine,
                         ServeConfig)

# calibrated on the fixed-seed reduced workload: bf16-vs-int8 greedy
# agreement sits well above this; near-tie argmax flips pull it under
# 1.0 but a correctness bug (wrong scales, wrong block) craters it
MATCH_THRESHOLD = 0.70
BYTES_RATIO_MAX = 0.55


def _arch_params(head_dim=64):
    arch = get_arch("qwen3-0.6b", reduced=True)
    arch = dataclasses.replace(
        arch, cfg=dataclasses.replace(arch.cfg, head_dim=head_dim))
    return arch, init_params(arch, jax.random.PRNGKey(0))


def _results(engine, workload):
    engine.reset()
    sched = ContinuousScheduler(engine)
    rids = [sched.submit(p, max_new_tokens=m) for p, m in workload]
    res = sched.run()
    return [res[r] for r in rids], sched


def check_identity(arch, params, emit, *, smoke):
    """Quantized paged == quantized slab exactly; ~= bf16 paged."""
    workload = make_workload(arch.vocab_size, 7, seed=3)
    sc = dict(batch_size=3, max_len=64, block_size=8)
    q_slab, _ = _results(
        Engine(arch, params, ServeConfig(batch_size=3, max_len=64,
                                         quantize_cache=True)), workload)
    bf16, _ = _results(
        PagedEngine(arch, params, ServeConfig(paged=True, paged_impl="jax",
                                              **sc)), workload)
    for impl in ("jax", "pallas"):
        out, _ = _results(
            PagedEngine(arch, params,
                        ServeConfig(paged=True, paged_impl=impl,
                                    quantize_cache=True, **sc)), workload)
        same = all(np.array_equal(a, b) for a, b in zip(q_slab, out))
        tot = sum(len(a) for a in bf16)
        hits = sum(int(np.sum(np.asarray(a[:len(b)]) == np.asarray(
            b[:len(a)]))) for a, b in zip(bf16, out))
        rate = hits / max(tot, 1)
        emit(f"quant_paged_identity_{impl}", 0.0,
             f"slab_identical={int(same)},bf16_match={rate:.3f}")
        if smoke:
            assert same, (f"quantized paged ({impl}) diverged from the "
                          "quantized slab engine")
            assert rate >= MATCH_THRESHOLD, (
                f"bf16 token match {rate:.3f} < {MATCH_THRESHOLD} "
                f"(quantization should only flip near-ties)")


def check_memory(arch, params, emit, *, smoke):
    """Live bytes/request <= 0.55x bf16 paging, scale pools counted."""
    def live_per_request(quant):
        eng = PagedEngine(arch, params, ServeConfig(
            batch_size=3, max_len=96, paged=True, block_size=8,
            paged_impl="jax", quantize_cache=quant))
        sched = ContinuousScheduler(eng, max_new_tokens=8)
        rng = np.random.default_rng(1)
        for n in (9, 17, 12):
            sched.submit(rng.integers(1, arch.vocab_size,
                                      (n,)).astype(np.int32))
        sched.step()                                   # all admitted
        live = eng.live_cache_bytes()
        per_req = live // max(sched.active, 1)
        sched.run()
        return per_req, eng._block_bytes

    bf16_req, bf16_blk = live_per_request(False)
    q_req, q_blk = live_per_request(True)
    ratio = q_req / max(bf16_req, 1)
    emit("quant_paged_live_bytes", 0.0,
         f"bf16_bytes_per_request={bf16_req},quant_bytes_per_request="
         f"{q_req},ratio={ratio:.3f},quant_block_bytes={q_blk},"
         f"bf16_block_bytes={bf16_blk}")
    if smoke:
        assert ratio <= BYTES_RATIO_MAX, (
            f"quantized paging uses {ratio:.3f}x the bf16 bytes/request "
            f"— want <= {BYTES_RATIO_MAX} with scales counted")
    return bf16_req, q_req


def check_hlo_hygiene(arch, params, emit, *, smoke):
    """Compiled quantized decode: no logits, no full-size dequant."""
    from repro.serve.engine import build_serve_fns

    sc = ServeConfig(batch_size=3, max_len=64, paged=True, block_size=8,
                     paged_impl="pallas", quantize_cache=True,
                     head_dtype="int8")
    eng = PagedEngine(arch, params, sc)
    *_, decode = build_serve_fns(eng.arch, sc)
    cur = np.zeros((3, 1), np.int32)
    txt = (jax.jit(decode)
           .lower(eng.params, eng.caches, cur, jax.random.PRNGKey(0))
           .compile().as_text())
    assert_logits_free(txt, 3, (arch.vocab_size, arch.padded_vocab))

    # every quantized operand whose full-size widening would betray an
    # out-of-kernel dequant: K/V pools, their gathered view, the lm_head
    cfg = eng.arch.cfg
    pool = None
    for leaf in jax.tree.leaves(
            eng.caches, is_leaf=lambda x: isinstance(x, dict)):
        if isinstance(leaf, dict) and "kp" in leaf:
            pool = leaf["kp"]
            break
    assert pool is not None, "no paged subtree in the quantized cache"
    n_pool, bs, nkv, hd = pool.shape[-4:]      # may carry a layer axis
    nb = sc.max_len // sc.block_size
    shapes = [pool.shape,                      # full (layer-stacked) pool
              (n_pool, bs, nkv, hd),           # one layer's pool
              (sc.batch_size, nb * bs, nkv, hd),       # gathered cache
              eng.params["lm_head"].shape]             # quantized head
    assert_no_wide_dequant(txt, shapes)
    emit("quant_hlo_hygiene", 0.0,
         f"logits_free=1,no_wide_dequant=1,shapes_checked={len(shapes)}")
    del cfg, smoke


def bench_quant(emit, *, smoke: bool = False):
    arch, params = _arch_params(head_dim=64)
    check_identity(arch, params, emit, smoke=smoke)
    check_memory(arch, params, emit, smoke=smoke)
    check_hlo_hygiene(arch, params, emit, smoke=smoke)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload + hard assertions (CI)")
    args = ap.parse_args(argv)

    def emit(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}")

    print("name,us_per_call,derived")
    bench_quant(emit, smoke=args.smoke)
    if args.smoke:
        print("smoke OK: quantized paged greedy identical to the "
              "quantized slab + bf16-matched above threshold, <= 0.55x "
              "live bytes/request with scales counted, logits-free and "
              "wide-dequant-free HLO")


if __name__ == "__main__":
    main()
