"""The paged KV pool is updated in place.

Every engine program that writes the pool donates it and returns it, and
the model step carries the stacked pool through its layer scan instead of
slicing each layer's pool out and stacking a new pool back.  The compiled
programs show both: the output aliases the donated pool, and the
temporaries do not grow with the pool.  The engine's tokens stay those of
static serve on every path that writes the pool.

XLA's CPU backend runs a bf16 scatter in f32 (it converts the whole
operand), so the compiled-program checks here hold the pool in f32;
``tests/test_tpu_compile.py`` makes the same check in bf16 for a described
TPU v5e.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn
from repro.models.model import build_model
from repro.models.transformer import attn_spec
from repro.serving import Engine, Request, poisson_trace, static_generate

KEY = jax.random.PRNGKey(0)
PAGE, SLOTS, MAX_LEN, CHUNK = 16, 4, 128, 16
# every jitted program of the engine that writes the pool
PROGRAMS = ("decode", "page_write", "chunk_prefill", "verify", "copy_page",
            "scatter_pages", "page_set")
SIZES = (64, 1024)


def pool_engine(cfg) -> Engine:
    """An engine with every pool-writing program built (a small pool: the
    programs are lowered at other pool sizes by shape)."""
    model = build_model(cfg)
    params = model.init(KEY)
    return Engine(model, params, max_slots=SLOTS, page_size=PAGE,
                  max_len=MAX_LEN, n_pages=17, prefill_chunk=CHUNK,
                  preemption=True, prefix_sharing=True,
                  prefix_cache_budget=1 << 20, spec_k=1, draft_params=params)


def pool_bytes(eng: Engine, n_pages: int) -> int:
    k = eng.pool["k"]
    return 2 * k.size // k.shape[2] * n_pages * k.dtype.itemsize


def lowered(eng: Engine, program: str, n_pages: int, sharding=None):
    """``program`` as the engine jits it, lowered with a pool of
    ``n_pages`` pages (shapes only: nothing is allocated)."""
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def side(shape_of):
        return {k: sds(shape_of(a.shape), a.dtype)
                for k, a in eng.pool.items()}

    pool = side(lambda s: s[:2] + (n_pages,) + s[3:])
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                    eng.params)
    b, w = eng.max_slots, eng.max_pages
    args = {
        "decode": (eng._decode, params, pool, sds((b, w)), sds((b, 1)),
                   sds((b,)), sds((b,))),
        "page_write": (eng._page_write, pool,
                       side(lambda s: s[:2] + (1, 2 * PAGE) + s[4:]),
                       sds((2,))),
        "chunk_prefill": (eng._chunk_prefill, params, pool, sds((1, w)),
                          sds((1, CHUNK)), sds(()), sds(())),
        "verify": (eng._verify, params, pool, sds((b, w)),
                   sds((b, eng.spec_k + 1)), sds((b,)), sds((b,))),
        "copy_page": (eng._copy_page, pool, sds(()), sds(())),
        "scatter_pages": (eng._scatter_pages, pool,
                          side(lambda s: s[:2] + (w,) + s[3:]),
                          sds((w,))),
        "page_set": (eng._page_set, pool,
                     side(lambda s: s[:2] + s[3:]), sds(())),
    }
    fn, *a = args[program]
    return fn.lower(*a)


def assert_in_place(eng: Engine, program: str, sharding=None) -> None:
    """The program's output aliases the whole donated pool, and its
    temporaries grow by less than one layer's K pool between 64 and 1024
    pages (a per-layer slice of the pool would grow them by at least
    two)."""
    small, big = (lowered(eng, program, n, sharding).compile()
                  .memory_analysis() for n in SIZES)
    n_layers = eng.model.cfg.n_layers
    layer_k = (pool_bytes(eng, SIZES[1]) - pool_bytes(eng, SIZES[0])) \
        // (2 * n_layers)
    assert big.alias_size_in_bytes >= pool_bytes(eng, SIZES[1])
    grown = big.temp_size_in_bytes - small.temp_size_in_bytes
    assert grown < layer_k, (grown, layer_k)


@pytest.fixture(scope="module")
def cpu_engine():
    cfg = configs.reduced(configs.get_config("internlm2-1.8b")).with_(
        dtype="float32")
    return pool_engine(cfg)


@pytest.mark.parametrize("program", PROGRAMS)
def test_pool_programs_update_in_place(cpu_engine, program):
    assert_in_place(cpu_engine, program)


def test_engine_step_donates_its_pool():
    """After a step the pool the engine held before is gone: a stale
    reference raises instead of reading old KV."""
    cfg = configs.reduced(configs.get_config("llama3.2-1b"))
    model = build_model(cfg)
    eng = Engine(model, model.init(KEY), max_slots=2, page_size=4,
                 max_len=16)
    eng.submit(Request(rid=0, tokens=np.arange(1, 6, dtype=np.int32),
                       max_new=4, arrival=0))
    old = eng.pool
    eng.warmup()
    assert old["k"].is_deleted() and old["v"].is_deleted()
    old = eng.pool
    eng.step()
    assert old["k"].is_deleted() and not eng.pool["k"].is_deleted()


# ---------------------------------------------------------------------------
# one layer of a stacked pool reads and writes like that layer alone
# ---------------------------------------------------------------------------
def _call(kind, params, x, pool, tables, spec, layer):
    if kind == "decode":
        return attn.paged_decode_attention(
            params, x[:, :1], pool, tables, jnp.asarray([5, 9], jnp.int32),
            spec, valid_len=jnp.asarray([64, 9], jnp.int32), layer=layer)
    if kind == "verify":
        return attn.paged_verify_attention(
            params, x, pool, tables, jnp.asarray([5, 9], jnp.int32),
            jnp.asarray([64, 10], jnp.int32), spec, layer=layer)
    return attn.paged_prefill_attention(
        params, x[:1], pool, tables[:1], jnp.asarray(4, jnp.int32),
        jnp.asarray(6, jnp.int32), spec, window=3, layer=layer)


@pytest.mark.parametrize("kind", ["decode", "verify", "prefill"])
def test_paged_kv_layer_of_stacked_pool(kind):
    """Layer (g, j) of a stacked (G, P, n_pages, ...) pool, addressed by
    its flat index, gives the same output and the same pages as the layer's
    own pool; every other layer is left byte for byte."""
    cfg = configs.reduced(configs.get_config("llama3.2-1b"))
    spec = attn_spec(cfg)
    params = attn.init_attention(KEY, cfg.d_model, spec)
    g, p, n_pages, page = 2, 2, 9, 4
    shape = (g, p, n_pages, page, spec.n_kv_heads, spec.head_dim)
    stacked = {"k": jax.random.normal(jax.random.PRNGKey(1), shape,
                                      jnp.bfloat16),
               "v": jax.random.normal(jax.random.PRNGKey(2), shape,
                                      jnp.bfloat16)}
    tables = jnp.asarray([[3, 5, 1, 7], [6, 2, 4, 8]], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, cfg.d_model),
                          jnp.bfloat16)
    gi, j = 1, 0
    alone = {k: a[gi, j] for k, a in stacked.items()}
    out_a, alone = _call(kind, params, x, alone, tables, spec, 0)
    out_s, after = _call(kind, params, x, stacked, tables, spec, gi * p + j)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_s))
    for k in ("k", "v"):
        want = np.asarray(stacked[k]).copy()
        want[gi, j] = np.asarray(alone[k])
        np.testing.assert_array_equal(np.asarray(after[k]), want)


# ---------------------------------------------------------------------------
# engine == static serve where the pool is carried and donated
# ---------------------------------------------------------------------------
ENGINE_CASES = {
    # the unrolled layer loop indexes the pool with a static layer
    "unrolled": ("llama3.2-1b", {"scan_layers": False},
                 {"prefill_chunk": 4, "preemption": True}),
    # pattern period 2 (local/global): the flat index covers g and j
    "windowed-chunked-spec": ("gemma2-27b", {"sliding_window": 6},
                              {"prefill_chunk": 4, "spec_k": 2}),
}


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
def test_engine_in_place_matches_static_serve(case):
    arch, over, kw = ENGINE_CASES[case]
    cfg = configs.reduced(configs.get_config(arch)).with_(**over)
    model = build_model(cfg)
    params = model.init(KEY)
    if kw.get("spec_k"):
        kw = {**kw, "draft_params": params}
    trace = poisson_trace(4, 0.7, max_prompt=10, max_new=6,
                          vocab=cfg.vocab, seed=5)
    eng = Engine(model, params, max_slots=2, page_size=4, max_len=24,
                 n_pages=9, **kw)
    res = eng.run(trace)
    assert res["stats"]["completed"] == len(trace)
    for req in trace:
        assert res["tokens"][req.rid] == static_generate(
            model, params, req), f"rid {req.rid}"
