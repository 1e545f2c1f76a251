"""Training driver: end-to-end LM training with SoD, checkpointing, fault
tolerance.  CPU-runnable (reduced configs) and mesh-ready (full configs).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \\
      --steps 200 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \\
      --steps 100 --sod tiled_csc --density 0.3
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.checkpoint import Checkpointer
from repro.core.sod import SoDConfig, sodify_params
from repro.data.pipeline import SyntheticLMData
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import LM
from repro.optim import AdamW, AdamWConfig, cosine_schedule
from repro.runtime.fault import FaultConfig, ResilientRunner


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--sod", choices=("tiled_csc", "block_csr"), default=None)
    ap.add_argument("--density", type=float, default=0.3)
    ap.add_argument("--quantize", default="none",
                    choices=("none", "int8", "fp8", "codebook", "auto"),
                    help="packed value quantization: int8/fp8 store "
                         "per-tile-scaled codes, codebook an EIE-style "
                         "shared-value table + 4-bit indices; 'auto' lets "
                         "the planner pick per layer under its accuracy "
                         "drift budget (requires --plan auto)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="warm the kernel tuning cache for this model's "
                         "packed weight shapes before training")
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
                         "(train steps, autotune measurements, kernel "
                         "dispatch) to PATH — open in Perfetto or "
                         "chrome://tracing; see docs/observability.md")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write a counters/gauges/histograms metrics "
                         "snapshot to PATH")
    args = ap.parse_args(argv)
    use_compile_cache()

    tracer = None
    if args.trace:
        # install before any instrumented call (autotune, dispatch)
        tracer = obs.install_tracer(obs.Tracer())
    mets = obs.Metrics() if args.metrics_json else None

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    if args.quantize != "none" and not args.sod:
        ap.error("--quantize requires Sparse-on-Dense packing "
                 "(pass --sod tiled_csc|block_csr)")
    if args.quantize == "auto" and args.plan != "auto":
        ap.error("--quantize auto needs the planner (pass --plan auto)")
    if args.sod:
        cfg = cfg.with_(sod=SoDConfig(
            mode=args.sod, density=args.density, min_dim=64,
            qmode=args.quantize if args.quantize != "auto" else "none"))
    model = LM(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    plan = None
    if args.plan and not cfg.sod.enabled:
        ap.error("--plan requires Sparse-on-Dense packing "
                 "(pass --sod tiled_csc|block_csr)")
    if cfg.sod.enabled:
        from repro.kernels import autotune
        from repro.runtime import planner

        # install the cache BEFORE planning: the planner's dispatch hints
        # must come from the same cache file dispatch will read
        cache = autotune.install_cache(args.tuning_cache)
        plan = planner.load_or_build(
            args.plan, params, cfg.sod, cfg=cfg, cache=cache,
            m_values=(args.batch * args.seq,),
            qmode="auto" if args.quantize == "auto" else None)
        if plan is not None:
            n_dense = sum(e.mode == "dense" for e in plan.entries.values())
            print(f"pack plan: {len(plan)} layers "
                  f"({len(plan) - n_dense} packed, {n_dense} dense), "
                  f"{plan.compressed_bytes():,} planned bytes")
        params = sodify_params(params, cfg.sod, plan=plan)
        from repro.core.sod import tree_weight_bytes
        print("sod weight bytes:", tree_weight_bytes(params))
        if args.autotune:
            if plan is not None:
                stats = planner.warmup_plan(
                    plan, (args.batch * args.seq,), cache=cache)
            else:
                stats = autotune.warmup_params(
                    params, (args.batch * args.seq,), cache=cache)
            print(f"autotune: {stats} -> {cache.path}")
    if args.plan_json and plan is not None:
        print(f"pack plan -> {plan.save(args.plan_json)}")

    opt = AdamW(AdamWConfig(lr=args.lr),
                schedule=cosine_schedule(args.lr, args.warmup, args.steps))
    opt_state = opt.init(params)
    data = SyntheticLMData(cfg, args.batch, args.seq, seed=args.seed)
    train_step = jax.jit(steps_mod.make_train_step(model, opt, plan=plan))
    ckpt = Checkpointer(args.ckpt_dir)

    state = {"params": params, "opt": opt_state}
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        start = ckpt.latest_step()
        state = ckpt.restore(start, state)
        print(f"resumed from step {start}")

    def do_step(step, state):
        batch = data.batch(step)
        p, o, metrics = train_step(state["params"], state["opt"], batch)
        state["params"], state["opt"] = p, o
        return metrics

    runner = ResilientRunner(
        step_fn=lambda step: do_step(step, state),
        checkpointer=ckpt,
        fault=FaultConfig(ckpt_every=args.ckpt_every),
        state_of=lambda: state,
        load_state=lambda s: state.update(s),
    )

    losses = []
    tr = obs.get_tracer()
    t0 = time.perf_counter()
    for step in range(start, args.steps):
        with tr.span("train_step", track="train", step=step):
            res = runner.run_step(step)
        loss = float(res.metrics["loss"])
        losses.append(loss)
        if mets is not None:
            mets.counter("train_steps")
            mets.observe("train_step_s", res.seconds)
        if step % args.log_every == 0 or step == args.steps - 1:
            toks = args.batch * args.seq
            print(f"step {step:5d}  loss {loss:7.4f}  "
                  f"lr {float(res.metrics['lr']):.2e}  "
                  f"gnorm {float(res.metrics['grad_norm']):6.3f}  "
                  f"{toks / max(res.seconds, 1e-9):,.0f} tok/s", flush=True)
    ckpt.save(args.steps - 1, state, blocking=True)
    dt = time.perf_counter() - t0
    summary = {
        "arch": cfg.name, "steps": args.steps,
        "first_loss": losses[0], "last_loss": losses[-1],
        "mean_last10": sum(losses[-10:]) / min(len(losses), 10),
        "wall_s": round(dt, 1),
    }
    if plan is not None:
        summary["plan_layers"] = len(plan)
        summary["plan_bytes"] = plan.compressed_bytes()
    if mets is not None:
        mets.gauge("wall_s", dt)
        pathlib.Path(args.metrics_json).write_text(
            json.dumps(mets.snapshot(), indent=2))
    if tracer is not None:
        summary["trace"] = str(tracer.export(args.trace))
        obs.install_tracer(None)
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
