"""``correct`` comes out true on a sound run and false on a broken one.

Runs the whole of ``run.py`` but its look for a chip, on the CPU at a tiny
size (two layers of width 256), with the timed path broken underneath: the
decode step that returns the KV pool unchanged, half of the batch left
undecoded, and every produced token altered.  The control, the program's
own fp8 value path, has to fail the same limits that sound runs pass.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""
from __future__ import annotations

import json
import sys

import jax.numpy as jnp
import pytest

import tiny

sys.path[:0] = [str(tiny.BENCH), str(tiny.BENCH.parent / "src")]

import run  # noqa: E402
from harness import checks, spec  # noqa: E402

CELLS = ("internlm2-1.8b-tcsc30.decode-batch",
         "internlm2-1.8b-tcsc30.chat-rate")
SECONDS = "4"
CONTROL = "fp8"         # the cell's control: values one step below bf16


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(tmp_path, capsys, cell, seed=3):
    root = tiny.make(tmp_path / "root")
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   SECONDS, "--trace", "0"], require_tpu=False, root=root,
                  base=root / "perfbench")
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check mean_gap")
    return line


def _break_decode(monkeypatch, fault):
    from repro.launch import steps

    make = steps.make_paged_decode_step

    def broken(model, mesh=None, plan=None):
        step = make(model, mesh=mesh, plan=plan)

        def run_step(params, pool, block_tables, tokens, pos, valid_len=None):
            nxt, logits, new_pool = step(params, pool, block_tables, tokens,
                                         pos, valid_len)
            return fault(nxt, tokens, pool, new_pool, logits)

        return run_step

    monkeypatch.setattr(steps, "make_paged_decode_step", broken)


FAULTS = {
    # the step returns its state (the KV pool) unchanged
    "state_unchanged": lambda nxt, tok, pool, new, lg: (nxt, lg, pool),
    # half of the batch left out: those rows repeat their input token
    "half_batch": lambda nxt, tok, pool, new, lg: (
        nxt.at[nxt.shape[0] // 2:].set(
            tok[nxt.shape[0] // 2:].reshape(nxt[nxt.shape[0] // 2:].shape)),
        lg, new),
    # every token altered where it is produced
    "token_altered": lambda nxt, tok, pool, new, lg: (
        (nxt + 1) % lg.shape[-1], lg, new),
}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, capsys, cell):
    line = _run(tmp_path, capsys, cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_incorrect(tmp_path, capsys, monkeypatch, cell,
                                        fault):
    _break_decode(monkeypatch, FAULTS[fault])
    line = _run(tmp_path, capsys, cell)
    assert line["correct"] is False, line["checks"]


def test_control_fails_where_the_program_passes(tmp_path, capsys):
    """The control on three seeds reads above a limit that the program's
    own runs keep."""
    import control

    root = tiny.make(tmp_path / "root")
    cell = spec.load(root / "BENCHMARK.json", root, CELLS[0],
                     root / "perfbench")
    ref = run._load_module(cell.reference, "perfbench_reference")
    for seed in (21, 22, 23):
        served = run.Run(cell, seed, float(SECONDS))
        sample = served.sample_and_free(seed)
        tokens, _ = checks.layout(sample, served.seq_len)
        _, prog_ok, _ = checks.compare(ref, cell.config, seed, sample,
                                       cell.limits, served.seq_len)
        cq = control.control_query(cell, seed, sample, tokens,
                                   served.seq_len, CONTROL)
        ctrl, ctrl_ok, _ = checks.compare(ref, cell.config, seed, sample,
                                          cell.limits, served.seq_len, cq)
        assert prog_ok
        assert not ctrl_ok, ctrl
    assert jnp.isfinite(ctrl["mean_gap"]["value"])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_the_cells_per_layer_metrics(
        tmp_path, capsys, monkeypatch, cell):
    """``--trace 1`` on the CPU, with the recorded chip trace standing in
    for the profiler's (the CPU has no device plane): the line carries
    the cell's per-layer metrics, ``busy_s`` and ``window_s``, and the
    breakdown."""
    from harness import xplane

    recorded = str(tiny.BENCH / "tests" / "data" /
                   "decode3_prefill1.xplane.pb")
    reduce = xplane.reduce
    monkeypatch.setattr(xplane, "reduce",
                        lambda path, window=None: reduce(recorded))
    root = tiny.make(tmp_path / "root")
    rc = run.main(["--workload", cell, "--seed", "8", "--seconds", SECONDS,
                   "--trace", "1"], require_tpu=False, root=root,
                  base=root / "perfbench")
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    # the kernels' rooflines need the trace's calls to match the steps
    # that ran, which a stand-in trace does not
    assert set(line["metrics"]) == {n for n in want
                                    if "roofline" not in n}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]
    assert line["correct"] is True
