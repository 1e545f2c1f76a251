#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program and its control.

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 10 [--controls fp8,int8,fp8_reference]

For each seed, in one process: serve the cell for a short window exactly as
``run.py`` does and judge the served tokens against the reference and the
cell's limits (the program's reading); then, on the same prompts and served
tokens, each control runs every sequence, and at every position the
reference reads the gap of the token the control puts first.  The controls
are a step below the configuration's bf16 weights:

* ``fp8``, ``int8``: the program's own quantized value path for packed
  matrices (``qmode``);
* ``fp8_reference``: the reference decoder itself computed in float8
  e4m3 (the configuration module's ``first_choices``): the control of a
  configuration served dense, whose program has no quantized path.

One JSON line per seed: for the program and each control, the numbers
compared beside the cell's limits and ``correct``, which has to come out
false for a control.  The benchmark's runs never run this; it sets the
limits in ``limits/<cell>.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run
from harness import build, checks, spec

QMODES = ("fp8", "int8")


def control_query(cell, seed: int, sample, tokens, seq_len: int,
                  control: str = "fp8"):
    """Query rows of the control: its first choice at every served
    position of the sample's sequences."""
    import jax
    import numpy as np

    from repro.launch import steps
    from repro.models.model import LM

    ref = run._load_module(cell.reference, "perfbench_reference")
    query = np.full(tokens.shape, -1, np.int32)
    if control == "fp8_reference":
        first = ref.first_choices(cell.config, seed, tokens)
        for b, (prompt, served) in enumerate(sample):
            plen, n = len(prompt), len(served)
            query[b, plen - 1:plen - 1 + n] = first[b, plen - 1:plen - 1 + n]
        return query
    if control not in QMODES:
        raise SystemExit(f"unknown control {control!r}")
    adapter = run._load_module(cell.adapter, "perfbench_adapter")
    mc = adapter.model_config(cell.config, qmode=control)
    if not mc.sod.enabled:
        raise SystemExit(f"{cell.config_name} packs nothing, so it has no "
                         f"{control} value path: use fp8_reference")
    model = LM(mc)
    params, _ = build.build_params(
        adapter, model, cell.config,
        lambda role, layer, *index: ref.checkpoint_matrix(
            cell.config, seed, role, layer, *index))
    prefill = jax.jit(steps.make_prefill_full(model))
    for b, (prompt, served) in enumerate(sample):
        plen, n = len(prompt), len(served)
        s = build.bucket(mc, cell.mix, plen + n - 1)
        padded = np.zeros((1, s), np.int32)
        padded[0, :plen + n - 1] = tokens[b, :plen + n - 1]
        logits, _ = prefill(params, {"tokens": jax.numpy.asarray(padded)})
        first = np.asarray(jax.numpy.argmax(logits[0], -1))
        query[b, plen - 1:plen - 1 + n] = first[plen - 1:plen - 1 + n]
    del params
    gc.collect()
    return query


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="fp8",
                    help="controls to read, comma-separated (fp8, int8, "
                         "fp8_reference)")
    args = ap.parse_args(argv)
    cell = spec.load(run.ROOT / "BENCHMARK.json", run.ROOT, args.workload)
    run._use_compile_cache()
    ref = run._load_module(cell.reference, "perfbench_reference")
    for seed in (int(s) for s in args.seeds.split(",")):
        served = run.Run(cell, seed, args.seconds)
        sample = served.sample_and_free(seed)
        seq_len = served.seq_len
        del served
        gc.collect()
        out = {"seed": seed}
        tokens, _ = checks.layout(sample, seq_len)
        for name in ["program", *args.controls.split(",")]:
            cq = None if name == "program" else control_query(
                cell, seed, sample, tokens, seq_len, name)
            nums, correct, info = checks.compare(
                ref, cell.config, seed, sample, cell.limits, seq_len, cq)
            out[name] = {"checks": nums, "correct": correct, **info}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
