"""Work counts against hand-computed numbers for one layer of the
configuration, and against the packed arrays of a tiny run."""
from __future__ import annotations

import json
import sys

import pytest

import tiny

sys.path[:0] = [str(tiny.BENCH), str(tiny.BENCH.parent / "src")]

import run  # noqa: E402
from harness import device, workcount  # noqa: E402

V5E = device.PEAKS["TPU v5 lite"]


def _layer(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
              "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return [workcount.Matrix(r, k, n, round(0.3 * k * n), 2)
            for r, (k, n) in shapes.items()]


def test_internlm2_layer_by_hand():
    cfg = json.loads((tiny.BENCH / "configs" / f"{tiny.CONFIG}.json")
                     .read_text())
    mats = _layer(cfg)
    # 2048^2 * 2 (q, o) + 2048 * 1024 * 2 (k, v) + 2048 * 8192 * 3 (mlp)
    assert sum(m.k * m.n for m in mats) == 62_914_560
    nnz = sum(m.nnz for m in mats)
    assert nnz == 18_874_369               # 30% of each matrix, rounded
    # one decode call of 64 rows through w_gate
    gate = next(m for m in mats if m.role == "w_gate")
    assert workcount.call_flops(64, gate) == 2 * 64 * 5_033_165
    assert workcount.call_bytes(64, gate) == 5_033_165 * 2 + 2 * 64 * 10_240
    # weight-streaming: bytes bound at 64 rows, compute bound at 4096
    assert workcount.least_time_s(64, gate, V5E) == pytest.approx(
        (10_066_330 + 1_310_720) / 819e9)
    assert workcount.least_time_s(4096, gate, V5E) == pytest.approx(
        2 * 4096 * 5_033_165 / 197e12)
    # one token at context 1000: weights twice, attention 4*ctx*H*hd per
    # layer (one layer here)
    one = dict(cfg, num_hidden_layers=1)
    assert workcount.token_ops(mats, 1000, one) == 2 * nnz + \
        4 * 1000 * 16 * 128


def test_packed_matrices_count_what_is_stored():
    import jax

    from harness import build
    from repro.models.model import LM

    cfg = json.loads((tiny.BENCH / "configs" / f"{tiny.CONFIG}.json")
                     .read_text())
    cfg.update(tiny.SIZES)
    model = LM(build.model_config(cfg))
    ref = run._load_module(tiny.BENCH / "configs" / f"{tiny.CONFIG}.py", "r")
    params, _ = build.build_params(
        model, cfg, lambda role, layer: ref.checkpoint_matrix(cfg, 7, role,
                                                              layer))
    mats = build.packed_matrices(params)
    # 7 projections in each of 2 layers, and the head
    assert len(mats) == 7 * 2 + 1
    want = {m.role: m.nnz for m in _layer(cfg)}
    want["head"] = round(0.3 * 256 * 512)
    for m in mats:
        # magnitude pruning keeps k weights, and every weight tied with the
        # k-th largest: bf16 magnitudes tie often (8 bits of mantissa)
        assert want[m.role] <= m.nnz <= want[m.role] * 1.03, m
        assert m.value_bytes == 2
    dense = params["blocks"]["mlp"]["w_up"].to_dense()
    assert int(jax.numpy.count_nonzero(dense[0, 0])) == next(
        m.nnz for m in mats if m.role == "w_up")
