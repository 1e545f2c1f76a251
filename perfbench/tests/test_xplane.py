"""The trace reduction on a small trace recorded on one TPU v5e: three
decode steps of internlm2-1.8b-tcsc30 at 64 slots and one admission with a
384-token prefill.  Checks the reduction against counts that follow from
what ran, and busy and idle time against the events themselves."""
from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import xplane  # noqa: E402

TRACE = HERE / "data" / "decode3_prefill1.xplane.pb"
MATMULS = 24 * 7 + 1          # packed matmuls in one forward: layers, head


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(str(TRACE))


def test_programs_and_kernel_calls(trace):
    assert trace.programs["decode"][0] == 3
    assert trace.programs["prefill"][0] == 1
    assert trace.programs["page_write"][0] == 1
    # one custom call per packed matmul of every forward that ran
    assert trace.kernels["pallas_fused"][0] == 4 * MATMULS
    assert "pallas_block" not in trace.kernels
    calls, seconds = trace.kernels["pallas_fused"]
    # kernels run inside the programs
    assert 0 < seconds < trace.programs["decode"][1] + \
        trace.programs["prefill"][1]


def test_busy_and_idle_add_up(trace):
    assert 0 < trace.busy_s <= trace.window_s
    idle = sum(s for _, s in trace.idle_gaps)
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    # the programs are serialised on the one chip: busy covers them all
    programs = sum(trace.programs[p][1] for p in ("decode", "prefill",
                                                 "page_write"))
    assert programs <= trace.busy_s * (1 + 1e-6)


def test_busy_is_the_union_of_op_intervals(trace):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(TRACE))
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(line for line in dev.lines if line.name == "XLA Ops")
    host = next(line for line in next(
        p for p in pd.planes if p.name == "/host:CPU").lines
        if line.name == "python")
    lo = next(e.end_ns for e in host.events if e.name.endswith("start_trace"))
    hi = next(e.start_ns for e in host.events if e.name.endswith("stop_trace"))
    # a millisecond grid: a cell is busy when any op overlaps it
    cells = set()
    for e in ops.events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        cells.update(range(int(s // 1e6), int(-(-t // 1e6))))
    grid = len(cells) * 1e-3
    # the grid rounds every busy interval out to whole milliseconds
    assert trace.busy_s <= grid <= trace.busy_s + 0.02


def test_idle_gaps_are_named_by_host_frames(trace):
    names = [n for n, _ in trace.idle_gaps]
    assert any(n.startswith("$engine.py") for n in names)
