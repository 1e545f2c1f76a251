"""The DeepSeek-V2 adapter on a tiny configuration
(``deepseek-v2-tiny.json`` over ``deepseek-v2-lite-ep8-tcsc30``): seeded
weights are the same on every call, every held expert's matrix is counted
at its routed share, the latent page is the engine's, and the cell runs
through ``run.py`` on the CPU, correct and with its per-layer metrics.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_deepseek_v2_adapter.py
"""
from __future__ import annotations

import hashlib
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

sys.path[:0] = [str(tiny.BENCH), str(tiny.BENCH.parent / "src")]

import run  # noqa: E402
from harness import build  # noqa: E402

CONFIG = "deepseek-v2-lite-ep8-tcsc30"
CELL = f"{CONFIG}.decode-batch"
SEED = 9


def _tiny_cfg() -> dict:
    cfg = json.loads((tiny.BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg.update(json.loads(
        (tiny.BENCH / "tests" / "deepseek-v2-tiny.json").read_text()))
    return cfg


def _modules():
    return (run._load_module(tiny.BENCH / "adapters" / "deepseek_v2.py",
                             "ds_adapter"),
            run._load_module(tiny.BENCH / "configs" / f"{CONFIG}.py",
                             "ds_reference"))


def _build(cfg, seed=SEED):
    from repro.models.model import LM

    adapter, ref = _modules()
    model = LM(adapter.model_config(cfg))
    params, _ = build.build_params(
        adapter, model, cfg,
        lambda role, layer, *i: ref.checkpoint_matrix(cfg, seed, role,
                                                      layer, *i))
    return adapter, model, params


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_seeded_weights_are_bit_equal_across_calls():
    cfg = _tiny_cfg()
    _, ref = _modules()
    for role, layer, idx in (("wq", 0, ()), ("expert_w_up", 1, (3,)),
                             ("router", 1, ()), ("head", 0, ())):
        a = ref.checkpoint_matrix(cfg, SEED, role, layer, *idx)
        b = ref.checkpoint_matrix(cfg, SEED, role, layer, *idx)
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32))), role
    assert _digest(_build(cfg)[2]) == _digest(_build(cfg)[2])
    # an expert's matrix depends on its id, not on which others are held
    a = ref.checkpoint_matrix(cfg, SEED, "expert_w_up", 1, 3)
    c = ref.checkpoint_matrix(cfg, SEED, "expert_w_up", 1, 2)
    assert not np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(c.astype(jnp.float32)))


def test_matmuls_list_each_held_expert_matrix_at_its_routed_share():
    cfg = _tiny_cfg()
    adapter, _, params = _build(cfg)
    mats = build.matmuls(params, adapter, cfg)
    share = cfg["num_experts_per_tok"] / cfg["router_experts"]
    experts = [m for m in mats if m.share == share]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert len(experts) == moe_layers * len(cfg["experts_held"]) * 3
    assert {m.role for m in experts} == {"w_gate", "w_up", "w_down"}
    assert all(m.kernel == "pallas_fused" for m in experts)
    assert all(m.share == 1.0 for m in mats if m not in experts)
    dense = {m.role for m in mats if m.kernel == "xla_dot"}
    assert {"wkv_a", "wkv_b", "router"} <= dense


def test_kv_page_bytes_is_the_engines_page():
    cfg = _tiny_cfg()
    adapter, model, params = _build(cfg)
    eng = build.engine(model, params, tiny.MIXES["decode-batch"], 64, 9)
    pool = jax.tree_util.tree_leaves(eng.pool)
    page_bytes = sum(a.size * a.dtype.itemsize for a in pool) // 9
    assert page_bytes == adapter.kv_page_bytes(model.cfg, 16)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    r = tiny.make(tmp_path / "root")
    (r / "perfbench" / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(_tiny_cfg()))
    return r


def test_cell_runs_correct_and_reports_expert_rows(root, capsys,
                                                   monkeypatch):
    """An untraced run reads ``correct``; a traced one, the recorded chip
    trace standing in for the profiler's, reports the cell's per-layer
    metrics but the roofline, and ``expert_rows.batch`` at the routed
    share: 4 slots x top-2 of 8 experts = 1 row per held expert a step."""
    from harness import xplane

    rc = run.main(["--workload", CELL, "--seed", "5", "--seconds", "4",
                   "--trace", "0"], require_tpu=False, root=root,
                  base=root / "perfbench")
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "output_tok_s"}
    assert '"router_near_ties"' in err

    recorded = str(tiny.BENCH / "tests" / "data" /
                   "decode3_prefill1.xplane.pb")
    reduce = xplane.reduce
    monkeypatch.setattr(xplane, "reduce",
                        lambda path, window=None: reduce(recorded))
    rc = run.main(["--workload", CELL, "--seed", "6", "--seconds", "4",
                   "--trace", "1"], require_tpu=False, root=root,
                  base=root / "perfbench")
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(line["metrics"]) == {n for n in want if "roofline" not in n}
    rows = line["metrics"]["expert_rows.batch"]["value"]
    assert 0.5 < rows < 1.5
