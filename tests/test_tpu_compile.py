"""The Pallas kernels compile for a TPU v5e at llama3.2-1b layer widths.

Interpret mode cannot see what the chip's compiler refuses (unaligned
slices, unlowerable primitives, block shapes, VMEM and SMEM overruns).  The
TPU compiler is installed with JAX and compiles for a described chip with
none attached, so these tests lower and compile each kernel, in every qmode
the build supports, for one chip of a described ``v5e:2x2``, and check that
the program holds a Mosaic kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import plan as plan_mod
from repro.core import sod
from repro.core.formats import fp8_dtype

D_MODEL, D_FF = 2048, 8192       # llama3.2-1b
TILE = (128, 128)
CAP = plan_mod.expected_cap(TILE[0], 0.3)
BCAP = TILE[0] // 8              # unstructured pruning keeps every block
QMODES = ("none", "int8", "codebook", "fp8")
# (K, N, M): decode rows of 8 slots (bm = 8) into the widest up projection,
# and a 256-token prefill through the K = 8192 down projection.
SHAPES = ((D_MODEL, D_FF, 8), (D_FF, D_MODEL, 256))


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _qmode(q):
    if q == "fp8" and fp8_dtype() is None:
        pytest.skip("this jax build has no float8_e4m3fn")
    return q


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n,m", SHAPES)
@pytest.mark.parametrize("qmode", QMODES)
def test_sod_matmul_compiles(one_chip, qmode, k, n, m):
    from repro.kernels.sod_matmul import sod_matmul_pallas

    w = _on(one_chip, sod._abstract_tiled((), k, n, jnp.bfloat16, TILE, CAP,
                                          qmode=_qmode(qmode)))
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    _assert_kernel(lambda x, w: sod_matmul_pallas(
        x, w, bm=min(m, 128), interpret=False), x, w)


@pytest.mark.parametrize("cap", [CAP, 72])
def test_sod_matmul_compiles_every_slot_chunk_dispatch_picks(one_chip, cap):
    """Every slot chunk the registry can dispatch for a cap (the prior's
    param space, canonicalized as the runner will run it) compiles; an
    unaligned chunk of 12 slots once did not."""
    from repro.kernels import registry
    from repro.kernels.sod_matmul import sod_matmul_pallas

    k, n, m = D_MODEL, D_FF, 8
    w = _on(one_chip, sod._abstract_tiled((), k, n, jnp.bfloat16, TILE, cap))
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    key = registry.problem_key(w, m=m, backend="interpret")
    impl = registry.get_impl("pallas_fused")
    chunks = {impl.canonical_params(key, p, m)["slot_chunk"]
              for p in impl.param_grid(key)}
    for chunk in sorted(chunks):
        _assert_kernel(lambda x, w: sod_matmul_pallas(
            x, w, bm=8, slot_chunk=chunk, interpret=False), x, w)


@pytest.mark.parametrize("k,n,m", SHAPES)
@pytest.mark.parametrize("qmode", QMODES)
def test_block_matmul_compiles(one_chip, qmode, k, n, m):
    from repro.kernels.block_matmul import block_matmul_pallas

    w = _on(one_chip, sod._abstract_block((), k, n, jnp.bfloat16, TILE, 8,
                                          BCAP, qmode=_qmode(qmode)))
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    _assert_kernel(lambda x, w: block_matmul_pallas(
        x, w, bm=min(m, 128), interpret=False), x, w)


@pytest.mark.parametrize("qmode", QMODES)
def test_decompress_compiles(one_chip, qmode):
    from repro.kernels.decompress import decompress_pallas

    w = _on(one_chip, sod._abstract_tiled((), D_FF, D_MODEL, jnp.bfloat16,
                                          TILE, CAP, qmode=_qmode(qmode)))
    _assert_kernel(lambda w: decompress_pallas(w, interpret=False), w)


@pytest.fixture(scope="module")
def v5e_pool_engine():
    from test_pool_in_place import pool_engine

    from repro import configs

    # the KV heads and head size tile the chip's (8, 128) vreg as the
    # published configurations do
    cfg = configs.reduced(configs.get_config("internlm2-1.8b")).with_(
        n_heads=8, n_kv_heads=8, head_dim=128)
    return pool_engine(cfg)


@pytest.mark.parametrize("program", [
    "decode", "page_write", "chunk_prefill", "verify", "copy_page",
    "scatter_pages", "page_set"])
def test_pool_programs_update_in_place_on_v5e(one_chip, v5e_pool_engine,
                                              program):
    """The engine's pool-writing programs, compiled for the chip with a
    bf16 pool, alias the donated pool and keep no per-layer copy of it
    (``tests/test_pool_in_place.py`` makes the check on the CPU)."""
    from test_pool_in_place import assert_in_place

    assert_in_place(v5e_pool_engine, program, sharding=one_chip)


@pytest.fixture(scope="module")
def v5e_latent_engine():
    from repro import configs
    from repro.models.model import build_model
    from repro.serving import Engine

    # DeepSeek-V2-Lite's cached row (latent 512 + roped key 64) on a
    # reduced model: the row, not the model's size, sets the pool's layout
    cfg = configs.reduced(configs.get_config("deepseek-v2-lite")).with_(
        kv_lora_rank=512, qk_rope_head_dim=64)
    model = build_model(cfg)
    return Engine(model, model.init(jax.random.PRNGKey(0)), max_slots=4,
                  page_size=128, max_len=512, n_pages=9)


@pytest.mark.parametrize("program", ["decode", "page_write"])
def test_latent_pool_programs_update_in_place_on_v5e(one_chip,
                                                     v5e_latent_engine,
                                                     program):
    """Latent attention's decode and page write, compiled for the chip,
    alias the donated pool, and their temporaries do not grow with it:
    rows padded to whole 128-lane tiles keep the pool position-major (a
    576-wide row was laid out position-minor and the pool copied whole)."""
    eng = v5e_latent_engine
    side = eng.pool["latent"]

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compiled(n_pages):
        pool = {"latent": sds(side.shape[:2] + (n_pages,) + side.shape[3:],
                              side.dtype)}
        if program == "page_write":
            cache = {"latent": sds(side.shape[:2] + (1, 256) + side.shape[4:],
                                   side.dtype)}
            return eng._page_write.lower(pool, cache, sds((2,))).compile()
        params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                        eng.params)
        b, w = eng.max_slots, eng.max_pages
        return eng._decode.lower(params, pool, sds((b, w)), sds((b, 1)),
                                 sds((b,)), sds((b,))).compile()

    small, big = (compiled(n).memory_analysis() for n in (64, 1024))
    page = side.size // side.shape[2] * side.dtype.itemsize
    assert big.alias_size_in_bytes >= 1024 * page
    grown = big.temp_size_in_bytes - small.temp_size_in_bytes
    assert grown < 960 * page // eng.model.cfg.n_layers, grown
