#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program and its control.

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 10 [--qmodes fp8,int8]

For each seed, in one process: serve the cell for a short window exactly as
``run.py`` does and judge the served tokens against the reference and the
cell's limits (the program's reading); then, on the same prompts and served
tokens, the control: the program's own fp8 value path (``qmode="fp8"``, a
step below the configuration's bf16 weights) prefills each sequence, and at
every position the reference reads the gap of the token the control puts
first.  ``--qmodes`` reads int8 values instead, or as well.  One JSON line
per seed: for the program and each control, the numbers compared beside the
cell's limits and ``correct``, which has to come out false for a control.
The benchmark's runs never run this; it sets the limits in
``limits/<cell>.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run
from harness import build, checks, spec


def control_query(cell, seed: int, sample, tokens, seq_len: int,
                  qmode: str = "fp8"):
    """Query rows of the control, the program with quantized values
    (``qmode``): its first choice at every served position of the
    sample's sequences."""
    import jax
    import numpy as np

    from repro.launch import steps
    from repro.models.model import LM

    mc = build.model_config(cell.config, qmode=qmode)
    model = LM(mc)
    ref = run._load_module(cell.reference, "perfbench_reference")
    params, _ = build.build_params(
        model, cell.config,
        lambda role, layer: ref.checkpoint_matrix(cell.config, seed, role,
                                                  layer))
    prefill = jax.jit(steps.make_prefill_full(model))
    query = np.full(tokens.shape, -1, np.int32)
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
    ap.add_argument("--qmodes", default="fp8",
                    help="controls to read, comma-separated (fp8, int8)")
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
        for name in ["program", *args.qmodes.split(",")]:
            cq = None if name == "program" else control_query(
                cell, seed, sample, tokens, seq_len, name)
            nums, correct, info = checks.compare(
                ref, cell.config, seed, sample, cell.limits, seq_len, cq)
            out[name] = {"checks": nums, "correct": correct, **info}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
