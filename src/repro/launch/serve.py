"""Serving launcher: continuous batching on the Pallas decode sampler.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --batch 4 --prompt-len 16 --max-new 16

Submits `--requests` (default: one per slot) prompts to the continuous
scheduler and prints per-request tokens plus throughput/occupancy.

Speculative decoding: pass ``--spec-draft <arch-id>`` (the draft model's
config; ``self`` drafts with the target model itself) and ``--spec-k N``
to decode through `serve.spec.SpecEngine` — each engine step emits up to
N+1 tokens.  ``--spec-self`` instead drafts from the TARGET model's own
multi-token-prediction heads (`serve.spec.SelfSpecEngine`, DESIGN.md §7):
no sidecar model, no second cache tree; ``--mtp-heads`` sets the head
count (default: spec-k).  ``--stats-json [PATH]`` dumps the scheduler's
run report (per-request TTFT/latency, tokens-per-step, acceptance rate,
spec mode) as JSON to PATH, or to stdout when no PATH is given.

Observability (DESIGN.md §11): ``--metrics-json [PATH]`` enables the
`repro.obs` registry before engine construction and dumps every
counter/gauge/histogram snapshot; ``--trace-out PATH`` additionally
records per-request lifecycle spans (``req.queue → req.prefill →
req.decode``) and engine/scheduler spans, exported as Chrome
``trace_event`` JSON (open in chrome://tracing / Perfetto) or JSONL
via ``--trace-format``.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, NamedTuple

import jax
import numpy as np

from repro import obs
from repro.configs.base import with_mtp
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import get_arch, init_params
from repro.serve import (ServeConfig, Engine, ContinuousScheduler,
                         SpecConfig, SpecEngine, SelfSpecEngine,
                         PagedEngine, PagedSelfSpecEngine)


class ServeRun(NamedTuple):
    """What one run leaves: the per-request outputs (generated tokens,
    or eval scores), the engine that served them and its scheduler."""
    out: np.ndarray
    engine: Any
    scheduler: ContinuousScheduler


def run(argv=None) -> ServeRun:
    """Parse `argv` as the command line, serve, and return the run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slots (continuous-batching batch size)")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests to submit (0: one per slot)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--sampler-impl", default="pallas",
                    choices=("pallas", "jax"))
    ap.add_argument("--spec-draft", default=None,
                    help="draft arch id for speculative decoding "
                         "('self': draft with the target model)")
    ap.add_argument("--spec-self", action="store_true",
                    help="self-speculate from the target's own MTP heads "
                         "(no sidecar draft model / cache tree)")
    ap.add_argument("--mtp-heads", type=int, default=0,
                    help="multi-token-prediction heads to attach "
                         "(0 with --spec-self: use --spec-k heads)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per speculative step")
    ap.add_argument("--paged", action="store_true",
                    help="paged block-pool KV cache with shared-prefix "
                         "reuse (serve/paged.PagedEngine, DESIGN.md §8)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged: tokens per KV block")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="paged: total pool blocks (0: dense-slab parity)")
    ap.add_argument("--paged-impl", default="pallas",
                    choices=("pallas", "jax"),
                    help="paged decode: Pallas kernel or gather oracle")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged: disable the shared-prefix trie")
    ap.add_argument("--quantize-cache", action="store_true",
                    help="int8 KV cache with per-(token, head) scales "
                         "(slab or paged; transformer family only)")
    ap.add_argument("--head-dtype", default=None,
                    metavar="DTYPE",
                    help="quantized lm_head serving dtype (int8, "
                         "float8_e4m3fn, float8_e5m2; default: full "
                         "precision)")
    ap.add_argument("--stats-json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="dump the scheduler stats report as JSON "
                         "(to stdout when PATH is omitted)")
    ap.add_argument("--metrics-json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="enable the repro.obs registry and dump every "
                         "instrument's snapshot as JSON (stdout when "
                         "PATH is omitted)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable per-request span tracing and write the "
                         "trace to PATH")
    ap.add_argument("--trace-format", default="chrome",
                    choices=("chrome", "jsonl"),
                    help="trace export format for --trace-out")
    ap.add_argument("--mode", default="generate",
                    choices=("generate", "eval"),
                    help="'eval': score --eval-conts continuations per "
                         "prompt (batched loglikelihood, logits-free) "
                         "instead of generating")
    ap.add_argument("--eval-conts", type=int, default=4,
                    help="eval mode: continuations per prompt")
    ap.add_argument("--cont-len", type=int, default=8,
                    help="eval mode: tokens per continuation")
    ap.add_argument("--beams", type=int, default=0,
                    help="beam search width per request (COW slot forks "
                         "on --paged; 0: plain greedy/sampled decode)")
    ap.add_argument("--best-of", type=int, default=0,
                    help="best-of-n sampling width per request")
    ap.add_argument("--best-of-temp", type=float, default=1.0,
                    help="best-of-n sampling temperature")
    ap.add_argument("--grammar-mask", default=None, metavar="SPEC",
                    help="constrained decoding: allowed-token spec "
                         "('3,7,42' | 'range:lo-hi' | 'even' | 'odd'); "
                         "disallowed tokens can never be sampled")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # obs must be live BEFORE engines/schedulers bind their instruments
    if args.metrics_json is not None or args.trace_out is not None:
        obs.enable(trace=args.trace_out is not None)

    if args.spec_self and args.spec_draft:
        ap.error("--spec-self and --spec-draft are mutually exclusive")
    if args.paged and args.spec_draft:
        ap.error("--paged supports plain and --spec-self decoding; the "
                 "sidecar draft engine keeps its dense slabs")
    modes_used = (args.mode == "eval" or args.beams or args.best_of
                  or args.grammar_mask)
    if modes_used and (args.spec_draft or args.spec_self):
        ap.error("--mode eval / --beams / --best-of / --grammar-mask "
                 "need the plain one-token engines (no --spec-*)")
    if args.beams and args.best_of:
        ap.error("--beams and --best-of are mutually exclusive")
    if (args.beams or args.best_of) and args.temperature != 0.0:
        ap.error("--beams/--best-of require --temperature 0 (best-of "
                 "sampling temperature is --best-of-temp)")
    if args.grammar_mask and (args.beams or args.best_of):
        ap.error("--grammar-mask cannot combine with --beams/--best-of")
    arch = get_arch(args.arch, reduced=args.reduced)
    if args.mtp_heads or args.spec_self:
        arch = with_mtp(arch, args.mtp_heads or args.spec_k)
    params = init_params(arch, jax.random.PRNGKey(args.seed))
    enc_len = 32 if arch.family == "encdec" else None
    fe = None
    if arch.family == "encdec":
        fe = jax.random.normal(
            jax.random.PRNGKey(1),
            (1, enc_len, arch.cfg.d_model)).astype(
                jax.numpy.dtype(arch.cfg.compute_dtype))
    sc = ServeConfig(batch_size=args.batch, max_len=args.max_len,
                     temperature=args.temperature, top_k=args.top_k,
                     top_p=args.top_p, sampler_impl=args.sampler_impl,
                     enc_len=enc_len,
                     paged=args.paged, block_size=args.block_size,
                     pool_blocks=args.pool_blocks,
                     paged_impl=args.paged_impl,
                     prefix_cache=not args.no_prefix_cache,
                     quantize_cache=args.quantize_cache,
                     head_dtype=args.head_dtype)
    if args.spec_self:
        cls = PagedSelfSpecEngine if args.paged else SelfSpecEngine
        eng = cls(arch, params, sc,
                  SpecConfig(k=min(args.spec_k, arch.mtp.n_heads)))
        mode = f"spec(self-mtp, heads={arch.mtp.n_heads}, k={eng.spec_k})"
        if args.paged:
            mode = "paged+" + mode
    elif args.spec_draft:
        if args.spec_draft == "self":
            draft_arch, draft_params = arch, params
        else:
            draft_arch = get_arch(args.spec_draft, reduced=args.reduced)
            draft_params = init_params(draft_arch,
                                       jax.random.PRNGKey(args.seed + 1))
        eng = SpecEngine(arch, params, sc, draft_arch, draft_params,
                         SpecConfig(k=args.spec_k))
        mode = f"spec(draft={args.spec_draft}, k={args.spec_k})"
    elif args.paged:
        eng = PagedEngine(arch, params, sc)
        mode = f"paged(block={args.block_size}, impl={args.paged_impl})"
    else:
        eng = Engine(arch, params, sc)
        mode = "continuous"
    rng = np.random.default_rng(args.seed)
    n_req = args.requests or args.batch
    prompts = rng.integers(1, arch.vocab_size,
                           (n_req, args.prompt_len)).astype(np.int32)

    sched = ContinuousScheduler(eng, max_new_tokens=args.max_new)
    t0 = time.perf_counter()
    if args.mode == "eval":
        mode = "eval+" + mode
        conts = [rng.integers(1, arch.vocab_size,
                              (args.eval_conts, args.cont_len)
                              ).astype(np.int32) for _ in prompts]
        rids = [sched.submit_eval(p, list(c), frontend_embeds=fe)
                for p, c in zip(prompts, conts)]
    elif args.beams:
        mode = f"beam{args.beams}+" + mode
        rids = [sched.submit_beam(p, n_beams=args.beams,
                                  frontend_embeds=fe) for p in prompts]
    elif args.best_of:
        mode = f"best_of{args.best_of}+" + mode
        rids = [sched.submit_best_of(p, n=args.best_of,
                                     temperature=args.best_of_temp,
                                     top_p=args.top_p,
                                     seed=args.seed + i,
                                     frontend_embeds=fe)
                for i, p in enumerate(prompts)]
    else:
        mask = None
        if args.grammar_mask:
            from repro.serve import parse_mask_spec
            mask = parse_mask_spec(args.grammar_mask,
                                   arch.vocab_size).astype(bool)
            mode = "constrained+" + mode
        rids = [sched.submit(p, frontend_embeds=fe, token_mask=mask)
                for p in prompts]
    results = sched.run()
    dt = time.perf_counter() - t0
    if args.mode == "eval":
        total = sum(sum(len(s) for s in results[r]) for r in rids)
        lls = [float(sum(s.sum() for s in results[r])) for r in rids]
        print(f"[serve] arch={arch.arch_id} mode={mode} scored "
              f"{len(rids)} prompts x {args.eval_conts} continuations "
              f"({total} tokens) in {dt:.2f}s ({total / dt:.1f} tok/s "
              f"incl. compile); mean loglikelihood "
              f"{np.mean(lls) / max(args.eval_conts, 1):.3f}")
    else:
        total = sum(len(results[r]) for r in rids)
        print(f"[serve] arch={arch.arch_id} mode={mode} served "
              f"{len(rids)} requests ({total} tokens) in {dt:.2f}s "
              f"({total / dt:.1f} tok/s "
              f"incl. compile; occupancy {sched.occupancy:.2f}, "
              f"{sched.decode_steps} decode steps, "
              f"{sched.tokens_per_step:.2f} tok/slot-step"
              + (f", acceptance {sched.acceptance_rate:.2f}"
                 if args.spec_draft or args.spec_self else "") + ")")
    if args.beams or args.best_of:
        hyp = sched.hypotheses[rids[0]]
        print(f"[serve] group[0]: {len(hyp)} hypotheses, best logp "
              f"{hyp[0].logp:.3f}, forks {sched.group_forks}, "
              f"pruned {sched.group_pruned}")
    if args.paged:
        ps = eng.paged_stats()
        if ps["enabled"]:
            pre = ps.get("prefix", {})
            print(f"[serve] paged: {ps['used_blocks']}/"
                  f"{ps['pool_blocks']} blocks live "
                  f"({ps['live_cache_bytes']} B), "
                  f"{ps['prefill_tokens']} prefill tokens, "
                  f"prefix hits {pre.get('hits', 0)} "
                  f"({pre.get('hit_tokens', 0)} tokens reused)")
        else:
            print(f"[serve] paged: family {arch.family!r} has no "
                  "pageable caches (dense-slab behavior)")
    if args.stats_json is not None:
        obs.export.dump_json(sched.stats(), args.stats_json,
                             label="stats", tag="serve")
    if args.metrics_json is not None:
        obs.export.dump_json(
            obs.export.metrics_report(obs.get_registry(),
                                      extra={"mode": mode,
                                             "arch": arch.arch_id}),
            args.metrics_json, label="metrics", tag="serve")
    if args.trace_out is not None:
        obs.export.write_trace(obs.get_tracer(), args.trace_out,
                               fmt=args.trace_format, tag="serve")
    if args.mode == "eval":
        out = np.stack([np.concatenate(
            [np.asarray(s, np.float32) for s in results[r]])
            for r in rids])
        print("[serve] sample scores:", np.round(out[0][:8], 3))
    else:
        out = np.stack([np.pad(np.asarray(results[r], np.int32),
                               (0, args.max_new - len(results[r])))
                        for r in rids])
        print("[serve] sample row:", out[0][:16])
    return ServeRun(out, eng, sched)


def main(argv=None):
    """Serve; returns the per-request outputs."""
    return run(argv).out


if __name__ == "__main__":
    use_compile_cache()
    main()
