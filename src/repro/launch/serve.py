"""Serving driver: batched prefill + greedy decode.

Attention families use the fused prefill (single forward building the KV
cache); recurrent/hybrid families rebuild their O(1) state by stepping the
prompt (exact, and how their caches behave in production continuation).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \\
      --batch 4 --prompt-len 32 --gen 16 --sod tiled_csc --density 0.3
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.core import plan as plan_mod
from repro.core.sod import SoDConfig, sodify_params
from repro.data.pipeline import SyntheticLMData
from repro.kernels import registry as kreg
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import LM


def prefill_cache(model: LM, params, prompt, max_len: int, plan=None):
    """Family-appropriate cache construction for a (B, S) prompt batch."""
    cfg = model.cfg
    b, s = prompt["tokens"].shape[:2]
    with plan_mod.use_plan(plan):
        if cfg.family in ("hybrid", "ssm"):
            cache = model.init_cache(b, max_len)
            logits = None
            step = jax.jit(model.decode_step)
            for t in range(s):
                tok = prompt["tokens"][:, t:t + 1]
                logits, cache = step(params, cache, tok, jnp.asarray(t))
            return logits[:, -1], cache, s
        last_logits, cache = jax.jit(
            lambda p, b_: model.prefill(p, b_))(params, prompt)
    # right-size the cache to max_len via the explicit per-family cache
    # geometry (the old shape-matching heuristic mis-grew any leaf whose
    # unrelated dim happened to equal the prompt length)
    cache = model.grow_cache(cache, max_len)
    return last_logits, cache, s


def _sample_tokens(outs, limit: int = 8) -> list[int]:
    """First generated token id per step for batch row 0, shape-agnostic.

    Step outputs differ by family — (B, 1) for token models, (B, 1, C) for
    the audio codebook stack — and the list may be shorter than ``limit``
    for small ``--gen`` (or empty for ``--gen 0``); indexing each step's
    array defensively handles all of them.
    """
    toks: list[int] = []
    for o in outs:
        a = np.asarray(o)
        if a.size == 0:
            continue
        toks.append(int(a.reshape(a.shape[0], -1)[0, 0]) if a.ndim >= 1
                    else int(a))
        if len(toks) >= limit:
            break
    return toks


def engine_main(args, model, params, plan, draft_params=None,
                draft_plan=None):
    """``--engine``: continuous batching over a synthetic Poisson trace."""
    from repro.serving import Engine, bucket_len, poisson_trace

    cfg = model.cfg
    page = args.page_size
    if cfg.family in ("hybrid", "ssm"):
        max_len = args.prompt_len + args.gen
    else:
        max_len = bucket_len(args.prompt_len, page, cfg.attn_chunk) + args.gen
    eng = Engine(model, params, max_slots=args.max_slots, page_size=page,
                 max_len=max_len, plan=plan,
                 prefill_chunk=args.prefill_chunk,
                 preemption=args.preemption,
                 prefix_sharing=args.prefix_sharing,
                 spec_k=args.spec_decode,
                 draft_params=draft_params, draft_plan=draft_plan,
                 prefix_cache_budget=args.prefix_cache_budget,
                 prefix_cache_dir=args.prefix_cache_dir)
    trace = poisson_trace(args.requests, args.arrival_rate,
                          max_prompt=args.prompt_len, max_new=args.gen,
                          vocab=cfg.vocab, seed=args.seed)
    res = eng.run(trace)
    if args.metrics_json:
        pathlib.Path(args.metrics_json).write_text(
            json.dumps(eng.metrics.snapshot(), indent=2))
    summary = {
        "engine": True, "arch": cfg.name, "requests": args.requests,
        "max_slots": args.max_slots,
        "page_size": page if eng.paged else None,
        "prefill_chunk": args.prefill_chunk,
        "preemption": args.preemption,
        "prefix_sharing": args.prefix_sharing,
        "spec_decode": args.spec_decode,
        "prefix_cache_budget": args.prefix_cache_budget,
        "prefix_cache_dir": args.prefix_cache_dir,
        "sample": res["tokens"][trace[0].rid][:8],
        **res["stats"],
    }
    if draft_plan is not None:
        summary["draft_density"] = draft_plan.meta.get("density_choice",
                                                       {}).get("chosen")
        summary["draft_bytes"] = draft_plan.compressed_bytes()
    return summary


@dataclasses.dataclass
class Served:
    """What :func:`build` makes from the command line: the model, its
    (optionally SoD-packed) params and pack plan, and the draft tier."""

    cfg: Any
    model: LM
    params: Any
    plan: Any = None
    draft_params: Any = None
    draft_plan: Any = None
    tune_stats: dict | None = None


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and cross-check the serving flags (exits on a bad combination,
    as argparse does)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--sod", choices=("tiled_csc", "block_csr"), default=None)
    ap.add_argument("--density", type=float, default=0.3)
    ap.add_argument("--quantize", default="none",
                    choices=("none", "int8", "fp8", "codebook", "auto"),
                    help="packed value quantization: int8/fp8 store "
                         "per-tile-scaled codes, codebook an EIE-style "
                         "shared-value table + 4-bit indices; 'auto' lets "
                         "the planner pick per layer under its accuracy "
                         "drift budget (requires --plan auto)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine mode: replay a "
                         "synthetic Poisson request trace (ragged "
                         "prompt/gen lengths) instead of one static batch")
    ap.add_argument("--requests", type=int, default=8,
                    help="engine mode: number of requests in the trace")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="engine mode: Poisson arrival rate, requests per "
                         "engine step")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="engine mode: running-batch capacity")
    ap.add_argument("--page-size", type=int, default=16,
                    help="engine mode: KV page size (attention families)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="engine mode: split prompt prefill into chunks of "
                         "this many tokens, interleaved with running decode "
                         "steps (attention families; default: fused "
                         "whole-prompt prefill)")
    ap.add_argument("--preemption", action="store_true",
                    help="engine mode: under pool pressure, swap the "
                         "youngest running sequence's KV pages to host "
                         "memory instead of blocking admission")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="engine mode: map identical prompt prefixes onto "
                         "refcounted KV pages (copy-on-write); requires "
                         "--prefill-chunk")
    ap.add_argument("--prefix-cache-budget", type=int, default=0,
                    metavar="BYTES",
                    help="engine mode: keep completed prompts' prefix "
                         "pages alive in HBM under this LRU byte budget, "
                         "demoting cold pages to host memory instead of "
                         "freeing them; requires --prefix-sharing "
                         "(0 with --prefix-cache-dir: pure host/disk "
                         "cache, nothing stays HBM-resident)")
    ap.add_argument("--prefix-cache-dir", default=None, metavar="DIR",
                    help="engine mode: spill demoted prefix pages to "
                         "DIR/<token-hash>.npz so the cache survives "
                         "engine restarts; requires --prefix-sharing")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="engine mode: speculative decoding — a second, "
                         "aggressively sparse pack of the same weights "
                         "drafts K tokens ahead per slot, verified in one "
                         "batched pass (greedy output stays bit-identical; "
                         "default: off).  Composes with --prefill-chunk, "
                         "--preemption, and --prefix-sharing: slots "
                         "mid-prefill sit out draft windows, and a "
                         "preempted slot's speculative pages are rolled "
                         "back, never swapped")
    ap.add_argument("--draft-sparsity", type=float, default=None,
                    help="fraction of draft-tier weights pruned away "
                         "(density = 1 - sparsity); default: let the "
                         "planner's cost model pick from its ladder")
    ap.add_argument("--autotune", action="store_true",
                    help="warm the kernel tuning cache for this model's "
                         "packed weight shapes before serving")
    ap.add_argument("--tuning-cache", default=None,
                    help="tuning-cache JSON path (default: "
                         "$REPRO_TUNING_CACHE or ~/.cache/repro/"
                         "tuning_cache.json)")
    ap.add_argument("--plan", default=None,
                    help="pack plan: JSON path to replay (e.g. dumped by "
                         "dryrun --plan-json), or 'auto' to build one with "
                         "the planner; default: global-config packing")
    ap.add_argument("--plan-json", default=None,
                    help="write the effective pack plan to this path")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON timeline "
                         "(engine phases, request lifecycle, kernel "
                         "dispatch) to PATH — open in Perfetto or "
                         "chrome://tracing; see docs/observability.md")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write a counters/gauges/histograms metrics "
                         "snapshot to PATH")
    args = ap.parse_args(argv)
    if args.prefix_sharing and not args.prefill_chunk:
        ap.error("--prefix-sharing requires --prefill-chunk (prefill must "
                 "be able to start mid-prompt to skip shared positions)")
    if ((args.prefix_cache_budget or args.prefix_cache_dir)
            and not args.prefix_sharing):
        ap.error("--prefix-cache-budget/--prefix-cache-dir require "
                 "--prefix-sharing (the cache retains trie-held pages)")
    if args.spec_decode and not args.engine:
        ap.error("--spec-decode requires --engine (draft/verify windows "
                 "run against the paged KV cache)")
    if args.draft_sparsity is not None and not args.spec_decode:
        ap.error("--draft-sparsity requires --spec-decode")
    if args.quantize != "none" and not args.sod:
        ap.error("--quantize requires Sparse-on-Dense packing "
                 "(pass --sod tiled_csc|block_csr)")
    if args.quantize == "auto" and args.plan != "auto":
        ap.error("--quantize auto needs the planner (pass --plan auto)")
    if args.plan and not args.sod:
        ap.error("--plan requires Sparse-on-Dense packing "
                 "(pass --sod tiled_csc|block_csr)")
    return args


def build(args: argparse.Namespace) -> Served:
    """Model, random params from ``--seed``, and (with ``--sod``) the pack
    plan and packed params, tuned first when ``--autotune`` asks."""
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    if args.sod:
        cfg = cfg.with_(sod=SoDConfig(
            mode=args.sod, density=args.density, min_dim=64,
            qmode=args.quantize if args.quantize != "auto" else "none"))
    model = LM(cfg)
    # one compiled program instead of an eager dispatch per init op
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    tune_stats = None
    plan = None
    # prefill consumes (batch·prompt_len, K); decode (batch, K).  Engine
    # mode decodes max_slots rows and prefills one prompt at a time, at
    # the page-aligned bucket length for attention families (batch-1
    # decode-step replay, M=1, for the recurrent ones).
    if args.engine:
        if cfg.family in ("hybrid", "ssm"):
            m_values = (1, args.max_slots)
        elif args.prefill_chunk:
            m_values = (args.prefill_chunk, args.max_slots)
        else:
            from repro.serving import bucket_len

            m_values = (bucket_len(args.prompt_len, args.page_size,
                                   cfg.attn_chunk), args.max_slots)
    else:
        m_values = (args.batch * args.prompt_len, args.batch)
    draft_params = draft_plan = None
    if cfg.sod.enabled or args.spec_decode:
        from repro.kernels import autotune
        from repro.runtime import planner

        # install the cache BEFORE planning: the planner's dispatch hints
        # must come from the same cache file dispatch will read
        cache = autotune.install_cache(args.tuning_cache)
        if cfg.sod.enabled:
            plan = planner.load_or_build(
                args.plan, params, cfg.sod, cfg=cfg, cache=cache,
                m_values=m_values,
                qmode="auto" if args.quantize == "auto" else None)
        if args.spec_decode:
            # draft packs the RAW weights — must happen before the target
            # tier's sodify_params prunes them in place below
            draft_density = (None if args.draft_sparsity is None
                             else 1.0 - args.draft_sparsity)
            draft_cfg, draft_plan = planner.build_draft_plan(
                params, cfg.sod, draft_density=draft_density,
                spec_k=args.spec_decode, cfg=cfg, cache=cache,
                m_values=m_values)
            draft_params = sodify_params(params, draft_cfg, plan=draft_plan)
    if cfg.sod.enabled:
        params = sodify_params(params, cfg.sod, plan=plan)
        if args.autotune:
            if plan is not None:
                tune_stats = planner.warmup_plan(plan, m_values, cache=cache)
            else:
                tune_stats = autotune.warmup_params(params, m_values,
                                                    cache=cache)
            print(f"autotune: {tune_stats} -> {cache.path}")
    if args.plan_json and plan is not None:
        print(f"pack plan -> {plan.save(args.plan_json)}")
    return Served(cfg, model, params, plan, draft_params, draft_plan,
                  tune_stats)


def main(argv=None):
    """CLI entry point: static batched serving or the continuous-batching
    engine (``--engine``), with optional Sparse-on-Dense packing and
    speculative decoding.  Prints and returns a JSON summary."""
    args = parse_args(argv)
    use_compile_cache()
    tracer = None
    if args.trace:
        # install before any instrumented object exists: the engine,
        # scheduler, and kernel registry capture the global tracer
        tracer = obs.install_tracer(obs.Tracer())
    served = build(args)
    cfg, model, params, plan = (served.cfg, served.model, served.params,
                                served.plan)
    tune_stats = served.tune_stats

    if args.engine:
        with kreg.record_dispatches() as dispatch_log:
            summary = engine_main(args, model, params, plan,
                                  draft_params=served.draft_params,
                                  draft_plan=served.draft_plan)
        summary["kernel_dispatch"] = kreg.dispatch_counts(dispatch_log)
        if tune_stats is not None:
            summary["autotune"] = tune_stats
        if plan is not None:
            summary["plan_layers"] = len(plan)
            summary["plan_bytes"] = plan.compressed_bytes()
        _finish_trace(tracer, args, summary)
        print(json.dumps(summary))
        return summary

    data = SyntheticLMData(cfg, args.batch, args.prompt_len, seed=args.seed)
    prompt = {k: v for k, v in data.batch(0).items() if k != "targets"}
    max_len = args.prompt_len + args.gen

    tr = obs.get_tracer()
    mets = obs.Metrics() if args.metrics_json else None
    with kreg.record_dispatches() as dispatch_log:
        t0 = time.perf_counter()
        with tr.span("prefill", track="serve", batch=args.batch,
                     prompt_len=args.prompt_len):
            last_logits, cache, pos0 = prefill_cache(
                model, params, prompt, max_len, plan=plan)
        prefill_s = time.perf_counter() - t0

        decode = jax.jit(steps_mod.make_decode_step(model, plan=plan))
        tok = jnp.argmax(last_logits, axis=-1)
        if cfg.family == "audio":
            tok = tok.reshape(args.batch, 1, cfg.n_codebooks)
        else:
            tok = tok.reshape(args.batch, 1)
        outs = []
        # The first decode step pays the jit compile; timing it with the
        # rest is why the historical tokens/sec numbers were so noisy.
        # Report it as warmup and the remaining steps as steady-state
        # throughput.
        warmup_s = steady_s = 0.0
        t0 = time.perf_counter()
        for t in range(args.gen):
            ts = time.perf_counter()
            with tr.span("decode_step", track="serve", t=t):
                nxt, logits, cache = decode(params, cache, tok,
                                            jnp.asarray(pos0 + t, jnp.int32))
            tok = nxt.reshape(tok.shape)
            outs.append(nxt)
            if mets is not None:
                # host-side dispatch time per step (the device compute is
                # async past step 0); step 0 includes the jit compile
                mets.observe("decode_step_s", time.perf_counter() - ts)
            if t == 0:
                jax.block_until_ready(nxt)
                warmup_s = time.perf_counter() - t0
                t0 = time.perf_counter()
        if args.gen:
            jax.block_until_ready(outs[-1])
            steady_s = time.perf_counter() - t0 if args.gen > 1 else 0.0
        decode_s = warmup_s + steady_s
    if mets is not None:
        mets.counter("generated_tokens", args.batch * args.gen)
        mets.gauge("prefill_s", prefill_s)
        mets.gauge("warmup_s", warmup_s)
        mets.gauge("steady_s", steady_s)
        pathlib.Path(args.metrics_json).write_text(
            json.dumps(mets.snapshot(), indent=2))

    summary = {
        "arch": cfg.name, "batch": args.batch,
        "prompt_len": args.prompt_len, "generated": args.gen,
        "prefill_s": round(prefill_s, 3),
        "warmup_s": round(warmup_s, 3),
        "decode_tok_per_s": round(args.batch * args.gen / max(decode_s, 1e-9), 1),
        "steady_tok_per_s": round(
            args.batch * (args.gen - 1) / max(steady_s, 1e-9), 1)
        if args.gen > 1 else 0.0,
        "sample": _sample_tokens(outs),
    }
    summary["kernel_dispatch"] = kreg.dispatch_counts(dispatch_log)
    if tune_stats is not None:
        summary["autotune"] = tune_stats
    if plan is not None:
        summary["plan_layers"] = len(plan)
        summary["plan_bytes"] = plan.compressed_bytes()
    _finish_trace(tracer, args, summary)
    print(json.dumps(summary))
    return summary


def _finish_trace(tracer, args, summary) -> None:
    """Export the run's trace (when ``--trace``) and uninstall the global
    tracer so later runs in the same process start clean."""
    if tracer is None:
        return
    out = tracer.export(args.trace)
    obs.install_tracer(None)
    summary["trace"] = str(out)


if __name__ == "__main__":
    main()
