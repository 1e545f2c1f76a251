"""The seam between the harness and the program: the program's adapter
found by the configuration's ``model_type``, seeded matrices addressed by
role, layer and further indices, and a toy mixture of experts added as
files alone, with no harness file edited.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_adapters.py
"""
from __future__ import annotations

import hashlib
import json
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

sys.path[:0] = [str(tiny.BENCH), str(tiny.BENCH.parent / "src")]

import run  # noqa: E402
from harness import build, spec, weights, workcount  # noqa: E402

SEED = 7
# sha256 (first 16 hex digits) of the float32 values of each role's matrix
# at tiny sizes and SEED, as the benchmark made them before adapters: the
# seeded matrix at (role, layer) and the tcsc30 checkpoint (pruned but the
# embedding) at layer 1, or 0 outside the layers.  Equal digests keep
# every existing cell's weights bit-identical.
SEEDED = {
    "embed/0": "7fded22d8cd3901f", "head/0": "1df4cd9d709381b6",
    "wq/0": "8e104bfc20355d82", "wq/1": "c629e6607cd80060",
    "wk/0": "d567e65631233fe8", "wk/1": "6f4e84ac58f07e2d",
    "wv/0": "d7269473e5d841d9", "wv/1": "cab0b4d60ef3198d",
    "wo/0": "c3dd3289db84c824", "wo/1": "f3ace1a814dfb274",
    "w_gate/0": "ed0e826cf38cd160", "w_gate/1": "15a186335249752e",
    "w_up/0": "a10045b5e0449741", "w_up/1": "5bb3a85cfc3a5c3e",
    "w_down/0": "dcc879e52f2a3138", "w_down/1": "9dcd0a7941cbb7a2",
}
PRUNED = {
    "embed": "7fded22d8cd3901f", "head": "f48da2187c8e78eb",
    "wq": "1a3652600625030a", "wk": "6153d2ccd11b3915",
    "wv": "7963e1f7e778b23b", "wo": "7c8794185d262449",
    "w_gate": "488c0133f3d58221", "w_up": "2d72b73628d4514c",
    "w_down": "7a297b1ccb6dce5a",
}


def _digest(w) -> str:
    return hashlib.sha256(
        np.asarray(w.astype(jnp.float32)).tobytes()).hexdigest()[:16]


def _tiny(config: str):
    cfg = json.loads((tiny.BENCH / "configs" / f"{config}.json").read_text())
    cfg.update(tiny.SIZES)
    return cfg, run._load_module(tiny.BENCH / "configs" / f"{config}.py",
                                 f"reference_{config}")


@pytest.mark.parametrize("name", sorted(SEEDED))
def test_seeded_matrix_keeps_its_bits(name):
    role, layer = name.split("/")
    cfg, ref = _tiny(tiny.CONFIG)
    assert _digest(ref.seeded_matrix(cfg, SEED, role, int(layer))) == \
        SEEDED[name]


@pytest.mark.parametrize("role", sorted(PRUNED))
def test_checkpoint_matrix_keeps_its_bits(role):
    cfg, ref = _tiny(tiny.CONFIG)
    layer = 0 if role in ("embed", "head") else 1
    assert _digest(ref.checkpoint_matrix(cfg, SEED, role, layer)) == \
        PRUNED[role]


@pytest.mark.parametrize("role", ["head", "w_down"])
def test_dense_checkpoint_is_the_same_weights_unpruned(role):
    cfg, ref = _tiny(tiny.DENSE)
    _, packed = _tiny(tiny.CONFIG)
    dense = ref.checkpoint_matrix(cfg, SEED, role, 0)
    assert _digest(dense) == _digest(packed.seeded_matrix(cfg, SEED, role,
                                                          0))
    assert int(jnp.count_nonzero(dense)) == dense.size


def test_a_new_roles_key_does_not_move_when_another_is_added():
    _, ref = _tiny(tiny.CONFIG)
    assert weights.role_id("router", ref.ROLES) == 0xC5D2F225
    assert weights.role_id("wq", ref.ROLES) == 2

    def router(numbered, expert=3):
        return weights.layer_matrix(SEED, "router", 1, expert, shape=(8, 4),
                                    std=1.0, numbered=numbered)

    first = router(ref.ROLES)
    for more in ((), ref.ROLES + ("shared_gate",), ("shared_gate",)):
        assert jnp.array_equal(router(more), first)
    # the further index is part of the key
    assert not jnp.array_equal(router(ref.ROLES, expert=4), first)


def test_unknown_model_type_fails_with_the_adapter_list(tmp_path):
    root = tiny.make(tmp_path / "root")
    path = root / "perfbench" / "configs" / f"{tiny.CONFIG}.json"
    cfg = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cfg, model_type="mamba9")))
    with pytest.raises(SystemExit, match=r"'mamba9'.*\['internlm2'\]"):
        spec.load(root / "BENCHMARK.json", root,
                  f"{tiny.CONFIG}.decode-batch", root / "perfbench")


TOY = {"name": "toy-moe", "model_type": "toy_moe", "hidden_size": 256,
       "num_hidden_layers": 2, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 128, "vocab_size": 512,
       "n_routed_experts": 4, "num_experts_per_tok": 2,
       "moe_intermediate_size": 128, "n_shared_experts": 1,
       "shared_expert_intermediate_size": 256, "rope_theta": 10000,
       "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
       "sparsity": {"format": "dense"}}


def _toy_shape(role: str) -> tuple[int, int]:
    d, v, e = TOY["hidden_size"], TOY["vocab_size"], TOY["n_routed_experts"]
    f, fs = TOY["moe_intermediate_size"], \
        TOY["shared_expert_intermediate_size"]
    q = TOY["num_attention_heads"] * TOY["head_dim"]
    kv = TOY["num_key_value_heads"] * TOY["head_dim"]
    return {"embed": (v, d), "head": (d, v), "wq": (d, q), "wk": (d, kv),
            "wv": (d, kv), "wo": (q, d), "router": (d, e),
            "expert_w_gate": (d, f), "expert_w_up": (d, f),
            "expert_w_down": (f, d), "shared_w_gate": (d, fs),
            "shared_w_up": (d, fs), "shared_w_down": (fs, d),
            "shared_gate": (d, 1)}[role]


def _toy_checkpoint(role, layer, *index):
    shape = _toy_shape(role)
    return weights.layer_matrix(SEED, role, layer, *index, shape=shape,
                                std=shape[0] ** -0.5)


def _toy_root(tmp_path):
    """A checkout holding only the toy's files: its configuration, its
    cell and the test's adapter, beside the benchmark's own traffic."""
    root = tmp_path / "toy"
    base = root / "perfbench"
    for d in ("adapters", "configs", "traffic", "limits"):
        (base / d).mkdir(parents=True)
    shutil.copy(tiny.BENCH / "tests" / "adapters" / "toy_moe.py",
                base / "adapters")
    shutil.copy(tiny.BENCH / "traffic" / "decode-batch.json",
                base / "traffic")
    (base / "configs" / "toy-moe.json").write_text(json.dumps(TOY))
    (base / "limits" / "toy-moe.decode-batch.json").write_text(
        json.dumps({"limits": tiny.LIMITS}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy-moe",
                     "file": "perfbench/configs/toy-moe.json"}],
        "workloads": [{"name": "toy-moe.decode-batch", "config": "toy-moe",
                       "traffic": "decode-batch", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    return root


def test_toy_moe_is_added_as_files_alone(tmp_path):
    from repro.models.model import LM

    root = _toy_root(tmp_path)
    cell = spec.load(root / "BENCHMARK.json", root, "toy-moe.decode-batch",
                     root / "perfbench")
    assert cell.adapter == root / "perfbench" / "adapters" / "toy_moe.py"
    adapter = run._load_module(cell.adapter, "toy_adapter")
    model = LM(adapter.model_config(cell.config))
    params, _ = build.build_params(adapter, model, cell.config,
                                   _toy_checkpoint)
    mats = build.matmuls(params, adapter, cell.config)

    layers, e, k = 2, 4, 2
    routed = [m for m in mats if m.share != 1.0]
    assert len(routed) == layers * e * 3
    assert {m.share for m in routed} == {k / e}
    assert {m.kernel for m in mats} == {"xla_dot"}
    # a token uses the attention, the router, the shared expert and its
    # gate, k of the e routed experts, and the head
    d, f, fs = 256, 128, 256
    per_layer = (2 * d * 256 + 2 * d * 128 + d * e + 3 * d * fs + d
                 + k * 3 * d * f)
    assert workcount.weight_ops(mats) == pytest.approx(
        2 * (layers * per_layer + d * 512))
    # each expert's matrices are drawn from its own key
    gate = params["blocks"]["moe"]["w_gate"]
    assert gate.shape == (2, 1, e, d, f)
    assert not jnp.array_equal(gate[0, 0, 0], gate[0, 0, 1])
    assert jnp.array_equal(gate[1, 0, 2],
                           _toy_checkpoint("expert_w_gate", 1, 2))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 512)
    logits = model.apply(params, {"tokens": tokens})[0]
    assert bool(jnp.all(jnp.isfinite(logits[..., :512])))
    assert adapter.kv_page_bytes(model.cfg, 16) == 2 * 2 * 16 * 128 * 2
