"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

A host-device analysis tool: ``main`` pins this process and the children it
starts to the CPU platform with 512 virtual devices, so on a chip host it
never contends for the chip (one process per chip).

For each cell this script:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. constructs abstract params / optimizer state / inputs (ShapeDtypeStruct,
     zero allocation) with full sharding specs (DP/TP/EP + ZeRO-1, optional
     Sparse-on-Dense packed weights),
  3. ``jax.jit(step).lower(...).compile()`` — proving the distribution config
     is coherent: sharding mismatches, compile-time OOM or unsupported
     collectives all fail here,
  4. records ``memory_analysis`` / ``cost_analysis`` / per-collective bytes
     parsed from the partitioned HLO into a JSON row consumed by the
     roofline report (EXPERIMENTS.md §Dry-run/§Roofline).

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--sod tiled_csc]
"""
import argparse
import json
import os
import pathlib
import re
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import SHAPES, shape_applicable
from repro.core.sod import SoDConfig
from repro.launch import specs as specs_mod
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.models.model import LM
from repro.optim.adamw import AdamW, AdamWConfig
from repro.runtime import sharding as shard_mod

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / \
    "results" / "dryrun"

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1,
}
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict:
    """Per-chip bytes moved by each collective family (partitioned module →
    shapes are per-device).  all-reduce counts 2× (ring RS+AG)."""
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip().lstrip("%")
        m = re.match(r"[\w.\-]+\s*=\s*(.+)", stripped)
        if not m:
            continue
        body = m.group(1)
        for kind in _COLLECTIVES:
            if re.search(rf"\b{kind}(-start|-done)?\(", body):
                if kind == "all-to-all" and "all-to-all(" not in body:
                    pass
                shapes = _SHAPE_RE.findall(body.split("(")[0]) or \
                    _SHAPE_RE.findall(body)
                if not shapes:
                    continue
                nbytes = max(_shape_bytes(d, s) for d, s in shapes)
                mult = 2 if kind == "all-reduce" else 1
                out[kind] += nbytes * mult
                count[kind] += 1
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    return out


def _build_from_cfg(cfg, shape, mesh, plan=None):
    """jit'd step + abstract args for one (config × shape) on a mesh."""
    model = LM(cfg)
    params = specs_mod.abstract_params(
        model, cfg.sod if cfg.sod.enabled else None, plan=plan)
    p_specs = shard_mod.param_specs(params, cfg, mesh)
    p_sh = shard_mod.to_shardings(p_specs, mesh)
    inputs = specs_mod.input_specs(cfg, shape)

    if shape.kind == "train":
        opt = AdamW(AdamWConfig())
        opt_state = jax.eval_shape(opt.init, params)
        o_specs = shard_mod.opt_state_specs(opt_state, p_specs, mesh)
        o_sh = shard_mod.to_shardings(o_specs, mesh)
        b_specs = shard_mod.batch_specs(inputs["batch"], mesh)
        b_sh = shard_mod.to_shardings(b_specs, mesh)
        step = steps_mod.make_train_step(model, opt, mesh=mesh, plan=plan)
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None))
        args = (params, opt_state, inputs["batch"])
    elif shape.kind == "prefill":
        b_specs = shard_mod.batch_specs(inputs["batch"], mesh)
        b_sh = shard_mod.to_shardings(b_specs, mesh)
        step = steps_mod.make_prefill_step(model, mesh=mesh, plan=plan)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
        args = (params, inputs["batch"])
    else:  # decode
        c_specs = shard_mod.cache_specs(
            inputs["cache"], cfg, mesh, shape.global_batch,
            seq_len=shape.seq_len,
            seq_shard=os.environ.get("SOD_SEQ_SHARD_CACHE", "1") == "1")
        c_sh = shard_mod.to_shardings(c_specs, mesh)
        step = steps_mod.make_decode_step(model, mesh=mesh, plan=plan)
        jitted = jax.jit(
            step, in_shardings=(p_sh, c_sh, None, None),
            out_shardings=(None, None, c_sh),
            donate_argnums=(1,))
        args = (params, inputs["cache"], inputs["tokens"], inputs["pos"])
    return jitted, args


def _plan_for_cell(cfg, shape, mesh, plan_path: str | None):
    """Per-layer pack plan for a dry-run cell: replayed from ``plan_path``
    when given, else built by the planner against the cell's abstract
    shapes, mesh, and the persisted tuning cache."""
    if not cfg.sod.enabled:
        return None
    from repro.core.plan import ModelPlan
    from repro.runtime import planner

    if plan_path:
        return ModelPlan.load(plan_path)
    shapes = jax.eval_shape(lambda: LM(cfg).init(jax.random.PRNGKey(0)))
    m_probe = shape.global_batch * (shape.seq_len
                                    if shape.kind != "decode" else 1)
    return planner.build_plan(
        shapes, cfg.sod, cfg=cfg, mesh=mesh,
        m_values=(max(min(m_probe, 4096), 1), shape.global_batch))


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               sod_mode: str | None, density: float,
               scan_layers: bool = True, n_layers: int | None = None,
               plan_path: str | None = None):
    cfg = configs.get_config(arch).with_(scan_layers=scan_layers)
    if n_layers is not None:
        cfg = cfg.with_(n_layers=n_layers)
    if sod_mode:
        cfg = cfg.with_(sod=SoDConfig(mode=sod_mode, density=density))
    if cfg.family == "moe" and os.environ.get("SOD_MOE_BLOCKS", "1") == "1":
        dp = 32 if multi_pod else 16
        cfg = cfg.with_(moe_dispatch_blocks=dp)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = _plan_for_cell(cfg, shape, mesh, plan_path)
    jitted, args = _build_from_cfg(cfg, shape, mesh, plan=plan)
    return cfg, shape, mesh, jitted, args, plan


def _analyze(compiled) -> dict:
    out = {}
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or
                              getattr(mem, "temp_size_in_bytes", 0)),
        }
    except Exception as e:  # CPU backend may not implement it
        out["memory"] = {"error": str(e)[:200]}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        out["cost"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        }
    except Exception as e:
        out["cost"] = {"error": str(e)[:200]}
    try:
        out["collectives"] = collective_bytes(compiled.as_text())
    except Exception as e:
        out["collectives"] = {"error": str(e)[:200]}
    return out


def _group_size(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.hybrid_attn_every
    if cfg.family == "ssm":
        return cfg.slstm_every or 1
    return cfg.pattern_period


def _extrapolate(a1: dict, a2: dict, g1: int, g2: int, g_full: int) -> dict:
    """Linear-in-depth extrapolation from two shallow unrolled probes.

    Layer stacks are homogeneous per group, so every cost counter is affine
    in the group count: total(g) = outside + per_group·g.  Exact — no
    modelling assumption beyond homogeneity.
    """
    out = {}
    for sec in ("cost",):
        if "error" in a1.get(sec, {}) or "error" in a2.get(sec, {}):
            out[sec] = {"error": "probe failed"}
            continue
        out[sec] = {}
        for key in a1[sec]:
            per = (a2[sec][key] - a1[sec][key]) / (g2 - g1)
            outside = a1[sec][key] - per * g1
            out[sec][key] = outside + per * g_full
    c1, c2 = a1.get("collectives", {}), a2.get("collectives", {})
    coll = {}
    for key in _COLLECTIVES + ("total",):
        if key in c1 and key in c2:
            per = (c2[key] - c1[key]) / (g2 - g1)
            coll[key] = max(c1[key] - per * g1 + per * g_full, 0.0)
    out["collectives"] = coll
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             sod_mode: str | None = None, density: float = 0.3,
             probes: bool | None = None, plan_path: str | None = None,
             plan_out: str | None = None) -> dict:
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "sod": sod_mode or "dense", "density": density if sod_mode else 1.0,
    }
    cfg = configs.get_config(arch)
    if not shape_applicable(cfg, SHAPES[shape_name]):
        rec["status"] = "skipped"
        rec["reason"] = "full-attention arch; long_500k requires sub-quadratic"
        return rec

    # ---- 1) full-config compile (scan layers): THE dry-run gate ----------
    t0 = time.perf_counter()
    cfg, shape, mesh, jitted, args, plan = build_cell(
        arch, shape_name, multi_pod, sod_mode, density, scan_layers=True,
        plan_path=plan_path)
    from repro.kernels import registry as kreg

    with mesh, kreg.record_dispatches() as dispatch_log:
        compiled = jitted.lower(*args).compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 1)
    # which registry impls the traced step really ran (mesh fallbacks to
    # the XLA oracle are visible here instead of silent), plus compact
    # per-impl×source totals for tuned-cache coverage at a glance
    rec["kernel_dispatch"] = kreg.dispatch_summary(dispatch_log)
    rec["dispatch_counts"] = kreg.dispatch_counts(dispatch_log)
    if plan is not None:
        # the chosen per-layer plan, path → one-liner (format, tile, cap,
        # dispatch hint, SPMD partitioning)
        rec["pack_plan"] = plan.summary()
        rec["pack_plan_bytes"] = plan.compressed_bytes()
        if plan_out:
            plan.save(plan_out)
            rec["pack_plan_file"] = str(plan_out)
    full = _analyze(compiled)
    rec["memory"] = full["memory"]
    rec["cost_scan_hlo"] = full["cost"]          # while-bodies counted once
    rec["collectives_scan_hlo"] = full["collectives"]
    del compiled

    # ---- 2) depth-probe pair (unrolled) → exact extrapolated costs -------
    # XLA counts while-loop bodies once, so the scan numbers above undercount
    # by ~n_groups; two shallow unrolled probes give the exact affine law.
    if probes is None:
        probes = not multi_pod   # roofline table is single-pod only
    if probes:
        g = _group_size(cfg)
        g_full = cfg.n_layers // g
        analyses = []
        for n_groups in (1, 2):
            t0 = time.perf_counter()
            # probes replay the same plan as the gated cell (a replayed
            # plan's concrete-observed caps differ from freshly built
            # abstract budgets; probe shapes must match the cell's)
            _, _, pmesh, pjit, pargs, _ = build_cell(
                arch, shape_name, multi_pod, sod_mode, density,
                scan_layers=False, n_layers=g * n_groups,
                plan_path=plan_path)
            with pmesh:
                pcomp = pjit.lower(*pargs).compile()
            analyses.append(_analyze(pcomp))
            rec[f"probe{n_groups}_compile_s"] = round(time.perf_counter() - t0, 1)
            del pcomp
        ext = _extrapolate(analyses[0], analyses[1], 1, 2, g_full)
        rec["cost"] = ext["cost"]
        rec["collectives"] = ext["collectives"]
        rec["collectives"]["counts"] = analyses[1]["collectives"].get(
            "counts", {})
    rec["n_devices"] = mesh.devices.size
    rec["params_b"] = cfg.param_count()
    rec["active_params_b"] = cfg.active_param_count()
    rec["status"] = "ok"
    return rec


def _result_path(arch, shape, multi_pod, sod_mode) -> pathlib.Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    return RESULTS_DIR / f"{arch}__{shape}__{mesh}__{sod_mode or 'dense'}.json"


HOST_DEVICES = 512


def _pin_to_host() -> None:
    """Run on the CPU platform with ``HOST_DEVICES`` virtual devices, here
    and in every child (they inherit the environment).  Must run before
    anything in the process touches a JAX device."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={HOST_DEVICES}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    jax.config.update("jax_platforms", "cpu")


def main():
    _pin_to_host()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sod", choices=("tiled_csc", "block_csr"), default=None)
    ap.add_argument("--density", type=float, default=0.3)
    ap.add_argument("--plan", default=None,
                    help="replay a pack-plan JSON instead of building one")
    ap.add_argument("--plan-json", default=None,
                    help="dump the cell's per-layer pack plan to this path "
                         "(replayable by train/serve --plan)")
    ap.add_argument("--all", action="store_true",
                    help="run every cell in subprocesses")
    ap.add_argument("--force", action="store_true", help="recompute cached")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    if args.all:
        jobs = []
        for arch in configs.ARCH_NAMES:
            for shape in SHAPES:
                for mp in (False, True):
                    jobs.append((arch, shape, mp))
        failures = 0
        for arch, shape, mp in jobs:
            path = _result_path(arch, shape, mp, args.sod)
            if path.exists() and not args.force:
                print(f"[cached] {path.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if mp:
                cmd.append("--multi-pod")
            if args.sod:
                cmd += ["--sod", args.sod, "--density", str(args.density)]
            print(f"[run] {' '.join(cmd[3:])}", flush=True)
            r = subprocess.run(cmd, timeout=args.timeout,
                               cwd=pathlib.Path(__file__).resolve().parents[3])
            if r.returncode:
                failures += 1
        sys.exit(1 if failures else 0)

    if not (args.arch and args.shape):
        ap.error("--arch and --shape required (or --all)")
    if (args.plan or args.plan_json) and not args.sod:
        ap.error("--plan/--plan-json require --sod tiled_csc|block_csr")
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, args.sod,
                       args.density, plan_path=args.plan,
                       plan_out=args.plan_json)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "sod": args.sod or "dense",
               "status": "error", "traceback": traceback.format_exc()[-4000:]}
    path = _result_path(args.arch, args.shape, args.multi_pod, args.sod)
    path.write_text(json.dumps(rec, indent=2))
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback",)}, indent=2))
    if rec["status"] == "error":
        print(rec.get("traceback", ""), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
