"""DeepSeek-V2's latent attention and held-expert MoE against the plain
reference, on a tiny DeepSeek-V2-shaped configuration: 2 layers (one
dense, one MoE), 8 routed experts of which 4 held, top-2, 1 shared
expert, latent 32, roped key 16.

The configuration is the benchmark's ``deepseek-v2-lite-ep8-tcsc30`` with
the sizes of ``perfbench/tests/deepseek-v2-tiny.json``; the program is
built from its seeded, pruned and packed checkpoint through the
benchmark's adapter, and compared with the configuration's float32
reference, which imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import steps
from repro.models import layers, moe
from repro.models.model import LM
from repro.serving import Engine, Request
from repro.serving import engine as engine_mod
from repro.serving.engine import static_generate

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "perfbench"
CONFIG = "deepseek-v2-lite-ep8-tcsc30"
SEED = 11
# bf16 program against the float32 reference at this size: prefill and
# absorbed decode read max |logit difference| 0.044-0.068 over seeds 11-13
# (logits' standard deviation ~0.89); the reference itself computed in
# float8 e4m3 reads 1.02-1.12 on them, so 0.15 separates the two
LOGIT_TOL = 0.15


def _load(path: pathlib.Path, name: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    """(model, params, cfg, reference) at the tiny size, seed ``SEED``."""
    sys.path.insert(0, str(BENCH))
    try:
        from harness import build

        cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
        cfg.update(json.loads(
            (BENCH / "tests" / "deepseek-v2-tiny.json").read_text()))
        adapter = _load(BENCH / "adapters" / "deepseek_v2.py", "ds_adapter")
        ref = _load(BENCH / "configs" / f"{CONFIG}.py", "ds_reference")
        model = LM(adapter.model_config(cfg))
        params, _ = build.build_params(
            adapter, model, cfg,
            lambda role, layer, *i: ref.checkpoint_matrix(cfg, SEED, role,
                                                          layer, *i))
    finally:
        sys.path.remove(str(BENCH))
    return model, params, cfg, ref


def _sequence(cfg, n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (1, n)).astype(np.int32)


def test_prefill_then_paged_absorbed_decode_agree_with_reference(tiny):
    """Fused prefill of a 24-token prompt, then 16 paged decode steps
    (absorbed attention over the latent pages) fed the sequence's own
    tokens: every position's logits lie within ``LOGIT_TOL`` of the
    reference's full forward pass, which a float8 e4m3 reference does not."""
    model, params, cfg, ref = tiny
    plen, n, page = 24, 40, 16
    tok = _sequence(cfg, n)
    want = ref.logits(cfg, SEED, tok)[0]

    padded = np.zeros((1, 32), np.int32)
    padded[0, :plen] = tok[0, :plen]
    logits, cache = jax.jit(steps.make_prefill_full(model))(
        params, {"tokens": jnp.asarray(padded)})
    got = [np.asarray(logits[0, :plen])]
    pool = model.init_paged_pool(8, page)
    pool = engine_mod._pool_write_pages(pool, cache, jnp.asarray([1, 2]))
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    decode = jax.jit(steps.make_paged_decode_step(model))
    for pos in range(plen, n):
        _, lg, pool, rows = decode(params, pool, tables,
                                   jnp.asarray(tok[:, pos:pos + 1]),
                                   jnp.asarray([pos], jnp.int32),
                                   jnp.asarray([n], jnp.int32))
        got.append(np.asarray(lg[0]))
        # one row, top-2 of 8 experts: it routes to 0, 1 or 2 held ones
        assert 0 <= int(rows) <= 2
    got = np.concatenate(got)[:, :cfg["vocab_size"]]
    assert np.abs(got - want).max() <= LOGIT_TOL

    h, _ = ref._hidden(cfg, SEED, tok, fp8=True)
    fp8 = np.asarray(h @ ref._matrix(cfg, SEED, "head", 0, fp8=True))[0]
    assert np.abs(fp8 - want).max() > LOGIT_TOL


def test_engine_greedy_tokens_equal_static_generate(tiny):
    model, params, cfg, _ = tiny
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg["vocab_size"],
                                               int(rng.integers(5, 40))),
                    max_new=int(rng.integers(3, 10)), arrival=i)
            for i in range(5)]
    eng = Engine(model, params, max_slots=3, page_size=16, max_len=64)
    eng.run(reqs)
    served = {s.req.rid: s.generated for s in eng._finished.values()}
    for r in reqs:
        assert served[r.rid] == static_generate(model, params, r,
                                                max_len=64), r.rid
    steps_log = [s for s in eng.metrics.steps.records if s.decode_rows]
    assert steps_log and all(s.expert_rows >= 0 for s in steps_log)
    assert sum(s.expert_rows for s in steps_log) > 0


def test_preemption_swaps_latent_pages_exactly():
    """Under pool pressure the engine swaps latent pages to the host and
    back, and every request still gets ``static_generate``'s tokens."""
    cfg = configs.reduced(configs.get_config("deepseek-v2-lite"))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab, 12), max_new=20,
                    arrival=0) for i in range(4)]
    eng = Engine(model, params, max_slots=4, page_size=4, max_len=32,
                 n_pages=14, preemption=True)
    eng.run(reqs)
    assert eng.stats["preemptions"] > 0
    served = {s.req.rid: s.generated for s in eng._finished.values()}
    for r in reqs:
        assert served[r.rid] == static_generate(model, params, r,
                                                max_len=32), r.rid


def _held_params(seed: int = 0, n_experts: int = 8, d: int = 32,
                 f: int = 64):
    spec = moe.HeldSpec(n_experts=n_experts, held=tuple(range(n_experts)),
                        top_k=2, d_model=d, d_ff=f, d_shared_ff=f)
    return spec, moe.init_held_moe(jax.random.PRNGKey(seed), spec,
                                   jnp.float32)


def _share(params, spec, held, x):
    """``held_moe``'s arguments on the chip holding experts ``held``."""
    idx = jnp.asarray(tuple(held))
    sub = {k: (v[idx] if k in ("w_gate", "w_up", "w_down") else v)
           for k, v in params.items()}
    return sub, x, moe.HeldSpec(**{**spec.__dict__, "held": tuple(held)})


def test_held_shares_add_up_to_the_uncut_layer():
    """Two chips holding experts 0-3 and 4-7 give, with the shared expert
    counted once, what the layer holding all 8 gives."""
    spec, params = _held_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    whole, rows = moe.held_moe(params, x, spec)
    a, ra = moe.held_moe(*_share(params, spec, range(4), x))
    b, rb = moe.held_moe(*_share(params, spec, range(4, 8), x))
    shared = layers.mlp(params["shared"], x)
    np.testing.assert_allclose(np.asarray(a + b - shared), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    assert int(ra) + int(rb) == int(rows) == 2 * 16 * 2


def test_held_moe_row_is_batch_independent_and_dropless():
    """A row's output is bitwise the same whatever else is in its batch,
    and with every row routed to the same two experts none is dropped."""
    spec, params = _held_params()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 32), jnp.float32)
    mixed = x.at[:, 1::2].set(y[:, 1::2])
    fn = jax.jit(lambda p, v: moe.held_moe(p, v, spec)[0])
    np.testing.assert_array_equal(np.asarray(fn(params, x)[:, ::2]),
                                  np.asarray(fn(params, mixed)[:, ::2]))

    # a router that sends every row to experts 0 and 1
    crowd = dict(params, router=jnp.zeros_like(params["router"])
                 .at[:, :2].set(1.0))
    x = jnp.abs(x)
    out, rows = moe.held_moe(crowd, x, spec)
    assert int(rows) == 2 * 32
    xt = x.reshape(-1, 32)
    probs = jax.nn.softmax(
        jnp.dot(xt, crowd["router"], precision="highest"), -1)
    want = layers.mlp(params["shared"], xt)
    for e in (0, 1):
        w = {k: params[k][e] for k in ("w_gate", "w_up", "w_down")}
        want = want + probs[:, e:e + 1] * layers.mlp(w, xt)
    np.testing.assert_allclose(np.asarray(out.reshape(-1, 32)),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_and_scale_match_the_formula():
    """DeepSeek-V2-Lite's YaRN: frequencies blended by a linear ramp over
    the correction range of beta_fast 32 and beta_slow 1 at 4096
    positions, factor 40; softmax scale mscale(40, 0.707)^2 / sqrt(192);
    cos/sin unscaled, as mscale == mscale_all_dim."""
    cfg = configs.get_config("deepseek-v2-lite")
    y, dim, base = cfg.yarn, cfg.qk_rope_head_dim, cfg.rope_theta

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(
        np.asarray(layers.yarn_frequencies(dim, base, y)), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert layers.yarn_softmax_scale(192, y) == pytest.approx(
        mscale ** 2 / math.sqrt(192))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 1, dim))
    pos = jnp.arange(5)[None]
    ang = np.arange(5)[:, None] * want
    x1, x2 = np.split(np.asarray(x)[0, :, 0], 2, -1)
    rot = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                          x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    np.testing.assert_allclose(
        np.asarray(layers.apply_rope(x, pos, base, y))[0, :, 0], rot,
        atol=1e-5)


def test_latent_page_holds_one_row_a_position_and_layer():
    """At published widths a page holds 27 layers x 128 positions x (512
    latent + 64 roped key, padded to 640 lanes) bf16 = 4.42 MB (InternLM2's
    keys and values: 12.58 MB)."""
    cfg = configs.get_config("deepseek-v2-lite")
    pool = jax.eval_shape(lambda: LM(cfg).init_paged_pool(3, 128))
    (name, a), = pool.items()
    assert name == "latent" and a.shape == (27, 1, 3, 128, 640)
    assert a.size // 3 * a.dtype.itemsize == 27 * 128 * 640 * 2 == 4_423_680


@pytest.mark.parametrize("feature", [
    {"prefill_chunk": 16}, {"prefill_chunk": 16, "prefix_sharing": True},
    {"spec_k": 2, "draft_params": {}}])
def test_engine_refuses_what_latent_attention_has_no_path_for(tiny, feature):
    model, params, _, _ = tiny
    with pytest.raises(ValueError, match="latent attention"):
        Engine(model, params, max_slots=2, page_size=16, max_len=64,
               **feature)


@pytest.mark.parametrize("fields", [
    {"experts_held": (0, 1)}, {"first_dense_layers": 1},
    {"d_ff_dense": 256}, {"kv_lora_rank": 32, "experts_held": ()}])
def test_options_of_the_latent_assembly_are_refused_elsewhere(fields):
    """The held-expert and leading-dense-layer options are read only by
    the latent assembly: a GQA configuration that sets them, or a latent
    MoE that holds no expert, is refused at init rather than served
    without them."""
    arch = "deepseek-v2-lite" if "kv_lora_rank" in fields else \
        "qwen2-moe-a2.7b"
    cfg = dataclasses.replace(configs.reduced(configs.get_config(arch)),
                              **fields)
    with pytest.raises(ValueError, match="experts_held|latent attention"):
        LM(cfg).init(jax.random.PRNGKey(0))
