#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU, at llama3.2-1b's published widths.

    python chip_smoke.py               # four serving phases on one chip
    python chip_smoke.py --four-chips  # sharded packed train step, 4 chips

The default run drives ``repro.launch.serve`` in ``--engine`` mode, in this
one process, through four phases: dense, ``--sod tiled_csc --density 0.3
--plan auto``, the same with ``--quantize int8``, and ``--sod block_csr``.
Weights are random, made from ``--seed``.  Each phase serves a few requests
and prints one JSON line: its set-up and compile seconds, ``steady_tok_per_s``
on the chip, the kernel dispatch counts and ``peak_bytes_in_use``.  Every
packed phase must dispatch each packed layer to a Pallas kernel under the
``tpu`` backend, and one prefill's logits must match the same pruned weights
decompressed and run through the dense path at ``highest`` precision.

``--four-chips`` runs only the sharded packed training step (loss and
gradients of ``launch/steps.make_loss_and_grads``) on a ``(data=2, model=2)``
mesh and compares it with the same step on one of those chips.

The script fails, exiting non-zero, when JAX finds no TPU, when any phase
raises, or when any check fails.  Its last line of output is the JSON object
``{"ok": true, "device": {...}}`` naming the device JAX reports.  Times are
smoke readings, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.formats import BlockCSR, TiledCSC  # noqa: E402
from repro.kernels import registry as kreg  # noqa: E402
from repro.launch import serve, steps  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "llama3.2-1b"
# Eight requests, prompts of 128-256 tokens, 16-32 new tokens each.  Pages of
# 128 tokens keep the prefill buckets to two shapes (128 and 256), so each
# phase compiles four programs rather than ten.
SERVE_FLAGS = ("--engine", "--arch", ARCH, "--requests", "8",
               "--prompt-len", "256", "--gen", "32", "--max-slots", "8",
               "--page-size", "128", "--arrival-rate", "4")
PHASES = {
    "dense": (),
    "tiled_csc": ("--sod", "tiled_csc", "--density", "0.3", "--plan", "auto"),
    "tiled_csc_int8": ("--sod", "tiled_csc", "--density", "0.3",
                       "--plan", "auto", "--quantize", "int8"),
    "block_csr": ("--sod", "block_csr", "--density", "0.3"),
}
PALLAS = ("pallas_fused", "pallas_block")

# Logits of the packed prefill against the dense reference.  Both paths
# multiply the same bf16 weights with bf16 activations and round every
# layer's output to bf16; they differ in accumulation order and in the
# reference's `highest` precision for float32 products.  One bf16 ulp is
# 2^-8 of a value, and a flipped rounding in one layer carries through the
# residual stream of all later ones, so the largest of the 256 x 128256
# errors is held to 10% of the reference logits' standard deviation.  A
# kernel that drops or misplaces even a few percent of the weights misses
# by several times that.
LOGITS_TOL_STD = 0.1
# The weights are random, so the largest logits of a 128256-way vocabulary
# sit close together (the top two differ by about a fifth of a standard
# deviation): a one-percent perturbation flips the argmax at some positions
# without any bug.  Require the top-1 token to agree at 80% of the prompt's
# positions; a broken kernel agrees at about 1/vocab.
TOP1_MIN = 0.8
# One-chip and four-chip loss and gradient norm of the same train step: the
# sharded step sums partial products in another order and the one-chip step
# runs the XLA reference kernel, both in bf16, so they agree to within 2%.
TRAIN_RTOL = 0.02


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _peak_bytes() -> int | None:
    """The device's peak bytes in use so far in this process."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_dispatch(log: list, packed: bool, backend: str) -> dict:
    """Dispatch counts of a phase; every packed layer must have run a
    Pallas kernel under ``backend``."""
    counts = kreg.dispatch_counts(log)
    if packed:
        _check(bool(log), "packed phase dispatched no packed matmul")
    wrong = sorted({f"{r['impl']}@{r['key'].backend}" for r in log
                    if r["impl"] not in PALLAS or r["key"].backend != backend})
    _check(not wrong, f"dispatched off the Pallas kernels or off the "
                      f"{backend!r} backend: {wrong}")
    return counts


def densify(params, dtype):
    """The same (pruned, possibly quantized) weights as dense arrays in the
    model's weight dtype — the values the kernels feed the MXU."""
    return jax.tree_util.tree_map(
        lambda w: (w.to_dense().astype(dtype)
                   if isinstance(w, (TiledCSC, BlockCSR)) else w),
        params, is_leaf=lambda w: isinstance(w, (TiledCSC, BlockCSR)))


def check_logits(served: serve.Served, prompt_len: int, seed: int) -> dict:
    """One prefill's logits through the packed path against the dense path
    over the decompressed weights at ``highest`` precision."""
    model = served.model
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, prompt_len),
                                0, served.cfg.vocab, jnp.int32)
    batch = {"tokens": tokens}
    got, _ = jax.jit(steps.make_prefill_full(model, plan=served.plan))(
        served.params, batch)
    dense_prefill = steps.make_prefill_full(model)

    def reference(params, batch):
        # decompress inside the program: eager scatters would materialize
        # their broadcast index arrays, gigabytes at these widths
        return dense_prefill(densify(params, served.cfg.dtype), batch)

    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(reference)(served.params, batch)
    got = np.asarray(got, np.float32)[0]
    want = np.asarray(want, np.float32)[0]
    err = float(np.max(np.abs(got - want)))
    std = float(np.std(want))
    top1 = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    out = {"max_abs_err": err, "ref_std": std, "err_over_std": err / std,
           "top1_agreement": top1, "tol_err_over_std": LOGITS_TOL_STD,
           "tol_top1": TOP1_MIN}
    _check(np.isfinite(got).all(), "packed prefill logits are not finite")
    _check(err <= LOGITS_TOL_STD * std,
           f"logits max abs err {err} > {LOGITS_TOL_STD} x ref std {std}")
    _check(top1 >= TOP1_MIN, f"top-1 agreement {top1} < {TOP1_MIN}")
    return out


def run_phase(name: str, phase_flags, *, base_flags=SERVE_FLAGS,
              backend: str = "tpu") -> dict:
    """Serve one phase through the engine and check it; returns its
    report line."""
    args = serve.parse_args([*base_flags, *phase_flags])
    start = time.perf_counter()
    served = serve.build(args)
    jax.block_until_ready(served.params)
    build_s = time.perf_counter() - start
    packed = bool(args.sod)
    with kreg.record_dispatches() as log:
        summary = serve.engine_main(args, served.model, served.params,
                                    served.plan)
        peak = _peak_bytes()             # before the reference adds its own
        logits = (check_logits(served, args.prompt_len, args.seed)
                  if packed else None)
    _check(summary["completed"] == args.requests,
           f"{summary['completed']} of {args.requests} requests completed")
    _check(summary["generated_tokens"] > 0, "no tokens generated")
    report = {
        "phase": name, "backend": kreg.current_backend(),
        "build_s": build_s, "warmup_compile_s": summary["warmup_s"],
        "steady_tok_per_s": summary["steady_tok_per_s"],
        "generated_tokens": summary["generated_tokens"],
        "kernel_dispatch": check_dispatch(log, packed, backend),
        "peak_bytes_in_use": peak,
    }
    if logits is not None:
        report["logits"] = logits
    report["phase_s"] = time.perf_counter() - start
    return report


def _grad_norm(grads) -> float:
    leaves = [g for g in jax.tree_util.tree_leaves(grads)
              if jnp.issubdtype(g.dtype, jnp.floating)]
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in leaves)))


def four_chip_phase(*, reduced: bool = False, batch: int = 8, seq: int = 256,
                    backend: str = "tpu") -> dict:
    """Loss and gradient norm of the packed train step on a (data=2,
    model=2) mesh against the same step on one of those chips."""
    from jax.sharding import Mesh

    from repro.core.sod import SoDConfig, sodify_params
    from repro.data.pipeline import SyntheticLMData
    from repro.models.model import LM
    from repro.runtime import planner
    from repro.runtime import sharding as shard_mod

    devs = jax.devices()
    _check(len(devs) >= 4, f"--four-chips needs 4 devices, have {len(devs)}")
    devs = devs[:4]
    mesh = Mesh(np.asarray(devs).reshape(2, 2), ("data", "model"))
    cfg = configs.get_config(ARCH)
    if reduced:
        cfg = configs.reduced(cfg)
    cfg = cfg.with_(sod=SoDConfig(mode="tiled_csc", density=0.3, min_dim=64))
    model = LM(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    plan = planner.build_plan(params, cfg.sod, cfg=cfg, mesh=mesh,
                              m_values=(batch * seq // 2, batch * seq))
    params = sodify_params(params, cfg.sod, plan=plan)
    data = SyntheticLMData(cfg, batch, seq, seed=0).batch(0)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    build_s = time.perf_counter() - t0

    p_sh = shard_mod.to_shardings(
        shard_mod.param_specs(params, cfg, mesh), mesh)
    b_sh = shard_mod.to_shardings(shard_mod.batch_specs(data, mesh), mesh)
    params4 = jax.device_put(params, p_sh)
    data4 = jax.device_put(data, b_sh)
    step4 = jax.jit(steps.make_loss_and_grads(model, mesh=mesh, plan=plan))
    t0 = time.perf_counter()
    with kreg.record_dispatches() as log4:
        loss4, _, grads4 = step4(params4, data4)
        jax.block_until_ready(grads4)
    first4_s = time.perf_counter() - t0

    params1 = jax.device_put(params, devs[0])
    data1 = jax.device_put(data, devs[0])
    step1 = jax.jit(steps.make_loss_and_grads(model, plan=plan))
    t0 = time.perf_counter()
    with kreg.record_dispatches() as log1:
        loss1, _, grads1 = step1(params1, data1)
        jax.block_until_ready(grads1)
    first1_s = time.perf_counter() - t0

    counts4 = kreg.dispatch_counts(log4)
    _check(bool(log4), "four-chip step dispatched no packed matmul")
    off = sorted({f"{r['impl']}@{r['key'].backend}|mesh={r['key'].mesh}"
                  for r in log4 if r["impl"] != "pallas_fused"
                  or not r["key"].mesh or r["key"].backend != backend})
    _check(not off, f"four-chip dispatch off mesh-keyed pallas_fused: {off}")

    per_dev = {str(d.id): 0 for d in devs}
    n_sharded = 0
    for leaf in jax.tree_util.tree_leaves((params4, grads4)):
        if not isinstance(leaf, jax.Array):
            continue                    # float0 cotangents of int leaves
        n_sharded += not leaf.sharding.is_fully_replicated
        for shard in leaf.addressable_shards:
            per_dev[str(shard.device.id)] += shard.data.nbytes
    _check(all(v > 0 for v in per_dev.values()),
           f"arrays do not reach every device: {per_dev}")
    _check(n_sharded > 0, "no parameter or gradient is sharded")

    l1, l4 = float(loss1), float(loss4)
    g1, g4 = _grad_norm(grads1), _grad_norm(grads4)
    report = {
        "phase": "four_chips", "mesh": dict(mesh.shape),
        "backend": kreg.current_backend(), "build_s": build_s,
        "first_step_s_4chip": first4_s, "first_step_s_1chip": first1_s,
        "loss_4chip": l4, "loss_1chip": l1,
        "grad_norm_4chip": g4, "grad_norm_1chip": g1,
        "loss_rel_diff": abs(l4 - l1) / abs(l1),
        "grad_norm_rel_diff": abs(g4 - g1) / abs(g1),
        "tol_rel": TRAIN_RTOL,
        "kernel_dispatch_4chip": counts4,
        "mesh_keys": sorted({r["key"].mesh for r in log4}),
        "kernel_dispatch_1chip": kreg.dispatch_counts(log1),
        "bytes_per_device": per_dev,
        "sharded_arrays": n_sharded,
    }
    _check(np.isfinite([l1, l4, g1, g4]).all(), "non-finite loss or grads")
    _check(report["loss_rel_diff"] <= TRAIN_RTOL,
           f"loss differs: 4 chips {l4} vs 1 chip {l1}")
    _check(report["grad_norm_rel_diff"] <= TRAIN_RTOL,
           f"grad norm differs: 4 chips {g4} vs 1 chip {g1}")
    return report


def main(argv=None) -> int:
    """Check the device, run the phases, print the report lines and the
    final ``{"ok": true, ...}`` line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (default device: "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 2
    if kreg.current_backend() != "tpu":
        print(f"chip_smoke: kernel dispatch backend is "
              f"{kreg.current_backend()!r}, not 'tpu' (is REPRO_SOD_BACKEND "
              f"set?)", file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache": use_compile_cache()}), flush=True)
    if args.four_chips:
        print(json.dumps(four_chip_phase()), flush=True)
    else:
        for name, flags in PHASES.items():
            print(json.dumps(run_phase(name, flags)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
