"""Kernel registry + autotuner: dispatch, cache round-trip, numerics."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # CI installs hypothesis; bare
    from _hypothesis_stub import given, settings, st  # envs skip these

from repro.core import formats, pruning
from repro.core.sod import SoDConfig, apply, pack_param
from repro.kernels import autotune, ops, ref, registry

KEY = jax.random.PRNGKey(7)


@pytest.fixture
def tmp_cache(tmp_path):
    cache = autotune.TuningCache(tmp_path / "tuning_cache.json")
    autotune.set_cache(cache)
    yield cache
    autotune.set_cache(None)


def _packed(shape=(256, 256), density=0.3, fmt="tiled_csc", seed=0):
    w = pruning.random_sparse(jax.random.fold_in(KEY, seed), shape, density)
    if fmt == "block_csr":
        w = pruning.block_prune(w, density)
        return w, formats.pack_block_csr(w)
    return w, formats.pack_tiled_csc(w)


# ---------------------------------------------------------------------------
# registry dispatch
# ---------------------------------------------------------------------------
def test_cpu_cold_cache_dispatches_jnp_oracle():
    _, p = _packed()
    impl, params = registry.choose(registry.problem_key(p, m=64,
                                                        backend="cpu"))
    assert impl.name == "jnp_oracle"
    assert impl.differentiable


def test_interpret_backend_dispatches_pallas():
    _, p = _packed()
    impl, _ = registry.choose(registry.problem_key(p, m=64,
                                                   backend="interpret"))
    assert impl.name == "pallas_fused"
    _, pb = _packed(fmt="block_csr")
    impl_b, _ = registry.choose(registry.problem_key(pb, m=64,
                                                     backend="interpret"))
    assert impl_b.name == "pallas_block"


def test_sod_config_auto_dispatches_through_registry_cpu_and_interpret():
    """Acceptance: SoDConfig(impl="auto") goes through the registry on both
    the CPU (jnp) and TPU-interpret (pallas) paths, numerically identical."""
    cfg = SoDConfig(mode="tiled_csc", density=0.4, min_dim=64)
    w = pruning.random_sparse(KEY, (256, 192), 0.4)
    p = pack_param(w, cfg, prune=False)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 256))
    want = np.asarray(x @ w)

    y_cpu = apply(x, p, cfg)                     # backend=cpu -> jnp oracle
    np.testing.assert_allclose(np.asarray(y_cpu), want, atol=5e-4, rtol=1e-4)

    registry.set_backend_override("interpret")   # -> pallas path
    try:
        y_int = apply(x, p, cfg)
    finally:
        registry.set_backend_override(None)
    np.testing.assert_allclose(np.asarray(y_int), want, atol=5e-4, rtol=1e-4)


def test_tpu_cold_cache_restricted_to_partitionable(monkeypatch):
    """Cold-cache TPU dispatch picks the Pallas kernel on one chip and on a
    mesh-qualified key; only an unwrapped dispatch in a multi-device
    process (where a pjit step could shard the weights, and pallas_call has
    no GSPMD rule) stays on natively partitionable impls.  A tuned entry
    still wins everywhere."""
    monkeypatch.setattr(autotune, "tpu_device_kind", lambda: "TPU v5 lite")
    _, p = _packed()
    key = registry.problem_key(p, m=256, backend="tpu")
    monkeypatch.setattr(registry.jax, "device_count", lambda *a: 1)
    impl, _ = registry.choose(key)
    assert impl.name == "pallas_fused"
    wrapped = registry.problem_key(p, m=256, backend="tpu",
                                   mesh="data=2,model=2|dp=data")
    assert registry.choose(wrapped)[0].name == "pallas_fused"
    impl_tuned, _ = registry.choose(
        key, tuned={"impl": "jnp_oracle", "params": {}})
    assert impl_tuned.name == "jnp_oracle"

    monkeypatch.setattr(registry.jax, "device_count", lambda *a: 4)
    impl, _ = registry.choose(key)
    assert impl.spmd_partitionable and not impl.requires_shard_map
    assert registry.choose(wrapped)[0].name == "pallas_fused"
    impl_tuned, _ = registry.choose(
        key, tuned={"impl": "pallas_fused", "params": {}})
    assert impl_tuned.name == "pallas_fused"


def test_tpu_prior_needs_known_device_kind(monkeypatch):
    """The TPU prior reads the chip's peaks from core.topology by
    device_kind: an unknown kind raises, and a host with no TPU cannot rank
    a TPU key at all — there is no default chip."""
    _, p = _packed()
    key = registry.problem_key(p, m=8, backend="tpu")
    with pytest.raises(RuntimeError, match="needs a TPU device"):
        autotune.rank_candidates(key)
    monkeypatch.setattr(autotune, "tpu_device_kind", lambda: "TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        autotune.rank_candidates(key)
    monkeypatch.setattr(autotune, "tpu_device_kind", lambda: "TPU v5 lite")
    assert autotune.rank_candidates(key)


def test_every_capable_impl_matches_ref():
    for fmt in ("tiled_csc", "block_csr"):
        w, p = _packed((300, 260), 0.25, fmt, seed=3)
        x = jax.random.normal(jax.random.fold_in(KEY, 2), (24, 300))
        fn_ref = (ref.sod_matmul_ref if fmt == "tiled_csc"
                  else ref.block_matmul_ref)
        want = np.asarray(fn_ref(x, p))
        for backend in ("cpu", "interpret"):
            key = registry.problem_key(p, m=24, backend=backend)
            for impl in registry.candidates(key):
                y = impl.run(x, p, backend=backend,
                             **impl.default_params(key))
                np.testing.assert_allclose(
                    np.asarray(y), want, atol=5e-4, rtol=1e-4,
                    err_msg=f"{impl.name} on {backend} ({fmt})")


def test_pallas_impls_differentiable_vs_oracle():
    """The custom VJPs must produce the oracle's gradients (incl. exact
    zeros at padding slots — fixed-mask training stays on the mask)."""
    w, p = _packed((300, 260), 0.25, seed=5)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (16, 300))
    impl = registry.get_impl("pallas_fused")
    params = impl.default_params(registry.problem_key(p, m=16,
                                                      backend="cpu"))

    def loss_pallas(x, p):
        return (impl.run(x, p, backend="cpu", **params) ** 2).sum()

    def loss_ref(x, p):
        return (ref.sod_matmul_ref(x, p) ** 2).sum()

    gx_p, gp_p = jax.grad(loss_pallas, argnums=(0, 1), allow_int=True)(x, p)
    gx_r, gp_r = jax.grad(loss_ref, argnums=(0, 1), allow_int=True)(x, p)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                               atol=2e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gp_p.vals), np.asarray(gp_r.vals),
                               atol=2e-2, rtol=1e-3)
    # padding slots carry exactly-zero gradient
    pad = np.asarray(p.rows) < 0
    assert np.all(np.asarray(gp_p.vals)[pad] == 0)


def test_block_vjp_matches_oracle():
    """pallas_block's custom VJP (tiles5 reshape + block_ids gather) must
    reproduce the oracle's gradients, with exact zeros at padding blocks."""
    w, pb = _packed((300, 260), 0.3, "block_csr", seed=6)
    x = jax.random.normal(jax.random.fold_in(KEY, 9), (16, 300))
    impl = registry.get_impl("pallas_block")
    params = impl.default_params(registry.problem_key(pb, m=16,
                                                      backend="cpu"))

    def loss_pallas(x, p):
        return (impl.run(x, p, backend="cpu", **params) ** 2).sum()

    def loss_ref(x, p):
        return (ref.block_matmul_ref(x, p) ** 2).sum()

    gx_p, gp_p = jax.grad(loss_pallas, argnums=(0, 1), allow_int=True)(x, pb)
    gx_r, gp_r = jax.grad(loss_ref, argnums=(0, 1), allow_int=True)(x, pb)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                               atol=2e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gp_p.block_vals),
                               np.asarray(gp_r.block_vals),
                               atol=2e-2, rtol=1e-3)
    pad = np.asarray(pb.block_ids) < 0
    assert np.all(np.asarray(gp_p.block_vals)[pad] == 0)


def test_k_slab_variants_match():
    """Non-resident K-slab (re-decompress per use) is numerically identical
    to the resident default."""
    from repro.kernels.sod_matmul import sod_matmul_pallas

    w, p = _packed((300, 260), 0.2, seed=9)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (64, 300))
    xp = jnp.pad(x, ((0, 0), (0, p.grid[0] * p.tile[0] - 300)))
    y0 = sod_matmul_pallas(xp, p, bm=64, k_slab=0)[:, :260]
    y1 = sod_matmul_pallas(xp, p, bm=64, k_slab=1)[:, :260]
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(x @ w),
                               atol=5e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------
def test_cache_roundtrip_warm_run_skips_measurement(tmp_cache):
    """Acceptance: cold-cache tune measures; warm-cache run (same process or
    a reload from disk) never re-measures."""
    _, p = _packed((256, 256), 0.3, seed=11)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (32, 256))
    calls = []

    def counting_measure(fn):
        calls.append(1)
        jax.block_until_ready(fn())
        return float(len(calls))

    entry = autotune.tune(x, p, backend="cpu", cache=tmp_cache,
                          measure_fn=counting_measure)
    assert calls, "cold cache must measure"
    assert entry["impl"] in registry.all_impls()
    n_cold = len(calls)

    # warm, same cache object
    autotune.tune(x, p, backend="cpu", cache=tmp_cache,
                  measure_fn=counting_measure)
    assert len(calls) == n_cold

    # warm, reloaded from disk
    reloaded = autotune.TuningCache(tmp_cache.path)
    assert len(reloaded) == len(tmp_cache)
    autotune.tune(x, p, backend="cpu", cache=reloaded,
                  measure_fn=counting_measure)
    assert len(calls) == n_cold

    # and the dispatcher consumes the persisted winner
    autotune.set_cache(reloaded)
    key = registry.problem_key(p, m=32, backend="cpu")
    impl, params = registry.choose(key, tuned=autotune.lookup(key))
    assert impl.name == entry["impl"]


def test_set_cache_pins_nondefault_path(tmp_path):
    """A cache installed via set_cache (launch --tuning-cache) must keep
    serving dispatch lookups even though its path differs from the env
    default — previously get_cache() silently evicted it."""
    cache = autotune.TuningCache(tmp_path / "pinned.json")
    autotune.set_cache(cache)
    try:
        assert autotune.get_cache() is cache
    finally:
        autotune.set_cache(None)


def test_tune_dedups_trials_on_canonical_params(tmp_cache):
    """bm values that clamp to the same effective block size must be
    measured once, and the cache must record what actually ran."""
    _, p = _packed((256, 256), 0.3, seed=19)
    x = jax.random.normal(jax.random.fold_in(KEY, 8), (8, 256))  # tiny M
    trials = []
    entry = autotune.tune(x, p, backend="interpret", cache=tmp_cache,
                          top_k=8, measure_fn=lambda fn: 1.0,
                          trials_out=trials)
    sigs = [(name, tuple(sorted(params.items())))
            for name, params, _ in trials]
    assert len(sigs) == len(set(sigs)), f"duplicate trials: {sigs}"
    # every pallas trial records the clamped bm (m=8 -> bm=8), not raw 128
    for name, params, _ in trials:
        if name == "pallas_fused":
            assert params["bm"] <= 8
    assert entry["params"] == dict(
        registry.get_impl(entry["impl"]).canonical_params(
            registry.problem_key(p, m=8, backend="interpret"),
            entry["params"], 8))


def test_cache_invalidated_by_kernel_hash(tmp_cache, monkeypatch):
    _, p = _packed((256, 256), 0.3, seed=13)
    x = jax.random.normal(jax.random.fold_in(KEY, 6), (32, 256))
    autotune.tune(x, p, backend="cpu", cache=tmp_cache,
                  measure_fn=lambda fn: 1.0)
    assert len(tmp_cache) == 1

    # simulate a kernel-source edit: stored hash no longer matches
    raw = json.loads(tmp_cache.path.read_text())
    raw["kernel_hash"] = "0" * 16
    tmp_cache.path.write_text(json.dumps(raw))
    stale = autotune.TuningCache(tmp_cache.path)
    assert len(stale) == 0


def test_tune_always_measures_the_default_config(tmp_cache):
    """The status-quo config is always a candidate, so the tuned choice can
    never silently lose to the seed's hard-coded parameters."""
    _, p = _packed((256, 256), 0.3, seed=17)
    x = jax.random.normal(jax.random.fold_in(KEY, 7), (32, 256))
    trials = []
    autotune.tune(x, p, backend="interpret", cache=tmp_cache,
                  measure_fn=lambda fn: 1.0, trials_out=trials)
    key = registry.problem_key(p, m=32, backend="interpret")
    fused = registry.get_impl("pallas_fused")
    default_canon = fused.canonical_params(key, fused.default_params(key), 32)
    assert ("pallas_fused", default_canon) in [
        (name, params) for name, params, _ in trials]


def test_warmup_params_covers_packed_tree(tmp_cache):
    cfg = SoDConfig(mode="tiled_csc", density=0.5, min_dim=64)
    params = {
        "wq": pack_param(pruning.random_sparse(KEY, (128, 128), 0.5), cfg,
                         prune=False),
        "w_up": pack_param(
            pruning.random_sparse(jax.random.fold_in(KEY, 1), (128, 256),
                                  0.5), cfg, prune=False),
        "bias": jnp.zeros((128,)),
    }
    stats = autotune.warmup_params(params, (16,), backend="cpu",
                                   cache=tmp_cache)
    assert stats["tuned"] == 2
    stats2 = autotune.warmup_params(params, (16,), backend="cpu",
                                    cache=tmp_cache)
    assert stats2 == {"tuned": 0, "cached": 2}


# ---------------------------------------------------------------------------
# property test: tuned output ≡ ref across formats (runs when hypothesis is
# installed, e.g. in CI)
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(
    k=st.integers(2, 5), n=st.integers(2, 5),
    density=st.floats(0.05, 0.9), fmt=st.sampled_from(
        ["tiled_csc", "block_csr"]),
    m=st.sampled_from([1, 8, 33]),
)
def test_tuned_dispatch_matches_ref_property(k, n, density, fmt, m):
    k, n = k * 64, n * 64
    w = pruning.random_sparse(jax.random.fold_in(KEY, k * n), (k, n), density)
    if fmt == "block_csr":
        w = pruning.block_prune(w, density)
        p = formats.pack_block_csr(w)
        fn_ref = ref.block_matmul_ref
    else:
        p = formats.pack_tiled_csc(w)
        fn_ref = ref.sod_matmul_ref
    x = jax.random.normal(jax.random.fold_in(KEY, m + k), (m, k))
    for backend in ("cpu", "interpret"):
        y = ops.sod_matmul(x, p, impl="auto", backend=backend)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(fn_ref(x, p)), atol=5e-4, rtol=1e-4,
            err_msg=f"{fmt} m={m} k={k} n={n} d={density:.2f} {backend}")
