"""SPMD execution layer on a fake 8-device CPU mesh.

The bulk of this module needs 8 jax devices and therefore runs in CI's
``spmd-tier`` job (``XLA_FLAGS=--xla_force_host_platform_device_count=8``
exported before pytest starts); without forced devices the mesh-dependent
tests skip.  One subprocess-isolated acceptance smoke always runs, so plain
tier-1 still proves the headline behaviour: a pjit-sharded ``sod_matmul``
dispatches a shard_map-wrapped Pallas impl (not the XLA oracle) and its
``jax.grad`` matches the dense reference.
"""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pruning
from repro.core.formats import BlockCSR, TiledCSC, pack_block_csr, \
    pack_tiled_csc
from repro.kernels import autotune, ops, ref, registry
from repro.runtime import spmd

KEY = jax.random.PRNGKey(11)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI spmd-tier job sets it)")


def _mesh():
    from repro.launch.mesh import make_fake_mesh

    return make_fake_mesh()


def _packed(shape=(256, 512), density=0.3, fmt="tiled_csc", seed=0):
    w = pruning.random_sparse(jax.random.fold_in(KEY, seed), shape, density)
    if fmt == "block_csr":
        w = pruning.block_prune(w, density)
        return w, pack_block_csr(w)
    return w, pack_tiled_csc(w)


@pytest.fixture
def interpret_backend():
    registry.set_backend_override("interpret")
    yield
    registry.set_backend_override(None)


# ---------------------------------------------------------------------------
# plan derivation / mesh keys
# ---------------------------------------------------------------------------
@needs_mesh
def test_auto_plan_shards_batch_and_columns():
    mesh = _mesh()
    _, p = _packed()                       # Nt = 4, divisible by model=2
    plan = spmd.auto_plan(mesh, p)
    assert plan.batch_axes == ("data",)
    assert plan.col_axis == "model"
    _, p_thin = _packed((256, 128))        # Nt = 1: no column sharding
    assert spmd.auto_plan(mesh, p_thin).col_axis is None


@needs_mesh
def test_mesh_key_in_tuning_cache_key():
    mesh = _mesh()
    _, p = _packed()
    plan = spmd.auto_plan(mesh, p)
    sig = f"{spmd.mesh_key(mesh)}|{plan.signature()}"
    local = spmd._local_packed(p, mesh, plan)
    key = registry.problem_key(local, m=16, backend="interpret", mesh=sig)
    s = autotune.key_str(key)
    assert "mesh=data=4,model=2" in s
    # same local problem without the mesh must land on a different entry
    key_plain = registry.problem_key(local, m=16, backend="interpret")
    assert autotune.key_str(key_plain) != s


@needs_mesh
def test_tuned_local_shard_entry_feeds_mesh_dispatch(tmp_path):
    """Per-local-shard tune() → the shard_map body's lookup hits it."""
    mesh = _mesh()
    _, p = _packed()
    plan = spmd.auto_plan(mesh, p)
    sig = f"{spmd.mesh_key(mesh)}|{plan.signature()}"
    local = spmd._local_packed(p, mesh, plan)
    cache = autotune.TuningCache(tmp_path / "cache.json")
    autotune.set_cache(cache)
    try:
        x_l = jax.random.normal(KEY, (12, 256))
        entry = autotune.tune(x_l, local, backend="interpret", mesh=sig,
                              cache=cache, measure_fn=lambda fn: 1.0)
        assert entry["impl"] == "pallas_fused"
        key = registry.problem_key(local, m=12, backend="interpret",
                                   mesh=sig)
        assert autotune.lookup(key) == entry
    finally:
        autotune.set_cache(None)


@needs_mesh
def test_warmup_params_spmd_counts_local_layouts(tmp_path):
    mesh = _mesh()
    _, p1 = _packed((256, 512), seed=1)
    _, p2 = _packed((256, 512), seed=2)    # same layout as p1 → one entry
    _, p3 = _packed((128, 256), seed=3)
    cache = autotune.TuningCache(tmp_path / "warm.json")
    stats = spmd.warmup_params_spmd(
        {"a": p1, "b": p2, "c": p3, "dense": jnp.zeros((4,))},
        (48,), mesh, backend="cpu", cache=cache)
    assert stats["tuned"] == 2
    stats2 = spmd.warmup_params_spmd(
        {"a": p1, "c": p3}, (48,), mesh, backend="cpu", cache=cache)
    assert stats2 == {"tuned": 0, "cached": 2, "skipped": 0}


# ---------------------------------------------------------------------------
# forward + grad correctness per plan
# ---------------------------------------------------------------------------
def _grads_vs_oracle(fn, x, p, fn_ref):
    g = jax.grad(lambda x, p: (fn(x, p) ** 2).sum(),
                 argnums=(0, 1), allow_int=True)(x, p)
    g_ref = jax.grad(lambda x, p: (fn_ref(x, p) ** 2).sum(),
                     argnums=(0, 1), allow_int=True)(x, p)
    return g, g_ref


@needs_mesh
@pytest.mark.parametrize("plan_kw,shape", [
    ({"batch_axes": ("data",)}, (300, 512)),
    ({"batch_axes": ("data",), "col_axis": "model"}, (300, 512)),
    # row parallelism shards Kt: K must tile evenly; ragged N instead
    ({"batch_axes": ("data",), "row_axis": "model"}, (512, 300)),
    ({"batch_axes": ("data",), "gather_axis": "model"}, (300, 512)),
    ({"gather_axis": "data"}, (300, 512)),
])
def test_plans_match_dense_reference(plan_kw, shape, interpret_backend):
    """Forward and jax.grad under every partition plan ≡ the dense
    reference, including exactly-zero grads at padding slots.  Ragged
    shapes exercise the pad-and-slice boundaries."""
    mesh = _mesh()
    w, p = _packed(shape, 0.25, seed=4)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (44, shape[0]))
    plan = spmd.SpmdPlan(**plan_kw)

    def fn(x, p):
        return spmd.sod_matmul_spmd(x, p, mesh=mesh, plan=plan)

    y = fn(x, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               atol=5e-4, rtol=1e-4)
    (gx, gp), (gx_r, gp_r) = _grads_vs_oracle(fn, x, p, ref.sod_matmul_ref)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                               atol=2e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gp.vals), np.asarray(gp_r.vals),
                               atol=2e-2, rtol=1e-3)
    pad = np.asarray(p.rows) < 0
    assert np.all(np.asarray(gp.vals)[pad] == 0)


@needs_mesh
def test_block_csr_spmd_grads(interpret_backend):
    mesh = _mesh()
    w, pb = _packed((256, 512), 0.3, "block_csr", seed=5)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (32, 256))

    def fn(x, p):
        return spmd.sod_matmul_spmd(
            x, p, mesh=mesh,
            plan=spmd.SpmdPlan(batch_axes=("data",), col_axis="model"))

    np.testing.assert_allclose(np.asarray(fn(x, pb)), np.asarray(x @ w),
                               atol=5e-4, rtol=1e-4)
    (gx, gp), (gx_r, gp_r) = _grads_vs_oracle(fn, x, pb,
                                              ref.block_matmul_ref)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                               atol=2e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gp.block_vals),
                               np.asarray(gp_r.block_vals),
                               atol=2e-2, rtol=1e-3)
    pad = np.asarray(pb.block_ids) < 0
    assert np.all(np.asarray(gp.block_vals)[pad] == 0)


# ---------------------------------------------------------------------------
# dispatch: shard_map-wrapped pallas, not the oracle
# ---------------------------------------------------------------------------
@needs_mesh
def test_mesh_dispatch_uses_pallas_not_oracle(interpret_backend):
    """Acceptance: under an active mesh, ops.sod_matmul auto-routes through
    the SPMD layer and the body dispatches a Pallas impl with a
    mesh-qualified problem key — not the XLA scatter+dot oracle."""
    mesh = _mesh()
    w, p = _packed(seed=6)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (48, 256))
    with mesh, registry.record_dispatches() as log:
        y = ops.sod_matmul(x, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               atol=5e-4, rtol=1e-4)
    assert log, "mesh dispatch did not consult the registry"
    assert all(rec["impl"] == "pallas_fused" for rec in log)
    assert all(rec["key"].mesh for rec in log)


@needs_mesh
def test_tpu_cold_cache_promotes_pallas_only_inside_wrapper(monkeypatch):
    """In a process that sees several devices the cold-cache TPU guard
    still pins *unwrapped* dispatch to natively partitionable impls, but
    the mesh-qualified key (inside shard_map) promotes the pallas kernels,
    and so does a process that sees one chip."""
    monkeypatch.setattr(autotune, "tpu_device_kind", lambda: "TPU v5 lite")
    _, p = _packed(seed=7)
    unwrapped, _ = registry.choose(
        registry.problem_key(p, m=64, backend="tpu"))
    assert not unwrapped.requires_shard_map
    wrapped, _ = registry.choose(
        registry.problem_key(p, m=64, backend="tpu", mesh="data=4|dp=data"))
    assert wrapped.name == "pallas_fused"
    monkeypatch.setattr(registry.jax, "device_count", lambda *a: 1)
    one_chip, _ = registry.choose(
        registry.problem_key(p, m=64, backend="tpu"))
    assert one_chip.name == "pallas_fused"


@needs_mesh
def test_opt_outs_respected(interpret_backend, monkeypatch):
    mesh = _mesh()
    w, p = _packed(seed=8)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (16, 256))
    with mesh, registry.record_dispatches() as log:
        ops.sod_matmul(x, p, spmd=None)            # explicit opt-out
    assert all(not rec["key"].mesh for rec in log)
    monkeypatch.setenv("REPRO_SPMD", "0")          # process-wide kill switch
    with mesh, registry.record_dispatches() as log2:
        ops.sod_matmul(x, p)
    assert all(not rec["key"].mesh for rec in log2)


# ---------------------------------------------------------------------------
# end-to-end: pjit-sharded model step
# ---------------------------------------------------------------------------
@needs_mesh
def test_pjit_train_step_runs_fused_kernels(interpret_backend):
    """A jit'd sharded train step on the fake mesh routes every packed
    matmul through the SPMD layer (forward and backward both trace), and
    the loss stays finite."""
    from repro import configs
    from repro.core.sod import SoDConfig, sodify_params
    from repro.data.pipeline import SyntheticLMData
    from repro.launch import steps as steps_mod
    from repro.models.model import LM
    from repro.optim.adamw import AdamW, AdamWConfig
    from repro.runtime import sharding as shard_mod

    mesh = _mesh()
    cfg = configs.reduced(configs.get_config("llama3.2-1b")).with_(
        sod=SoDConfig(mode="tiled_csc", density=0.4, min_dim=64))
    model = LM(cfg)
    params = sodify_params(model.init(jax.random.PRNGKey(0)), cfg.sod)
    opt = AdamW(AdamWConfig())
    opt_state = opt.init(params)
    data = SyntheticLMData(cfg, 8, 32, seed=0)
    batch = data.batch(0)

    p_specs = shard_mod.param_specs(params, cfg, mesh)
    p_sh = shard_mod.to_shardings(p_specs, mesh)
    o_sh = shard_mod.to_shardings(
        shard_mod.opt_state_specs(opt_state, p_specs, mesh), mesh)
    b_sh = shard_mod.to_shardings(shard_mod.batch_specs(batch, mesh), mesh)

    step = jax.jit(steps_mod.make_train_step(model, opt, mesh=mesh),
                   in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None))
    with mesh, registry.record_dispatches() as log:
        _, _, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    spmd_recs = [r for r in log if r["key"].mesh]
    assert spmd_recs, "no packed matmul went through the SPMD layer"
    assert {r["impl"] for r in spmd_recs} == {"pallas_fused"}


@needs_mesh
def test_sharded_grad_matches_unsharded_step(interpret_backend):
    """loss/grads of the mesh-sharded model ≡ the single-device model."""
    from repro import configs
    from repro.core.sod import SoDConfig, sodify_params
    from repro.data.pipeline import SyntheticLMData
    from repro.launch import steps as steps_mod
    from repro.models.model import LM

    cfg = configs.reduced(configs.get_config("llama3.2-1b")).with_(
        sod=SoDConfig(mode="tiled_csc", density=0.5, min_dim=64))
    model = LM(cfg)
    params = sodify_params(model.init(jax.random.PRNGKey(1)), cfg.sod)
    batch = SyntheticLMData(cfg, 4, 32, seed=1).batch(0)

    loss_ref, _, grads_ref = steps_mod.make_loss_and_grads(model)(
        params, batch)
    mesh = _mesh()
    loss_sh, _, grads_sh = steps_mod.make_loss_and_grads(model, mesh=mesh)(
        params, batch)
    np.testing.assert_allclose(float(loss_sh), float(loss_ref),
                               atol=1e-4, rtol=1e-4)
    for leaf_sh, leaf_ref in zip(
            jax.tree_util.tree_leaves(grads_sh),
            jax.tree_util.tree_leaves(grads_ref)):
        if leaf_sh.dtype == jax.dtypes.float0:
            continue
        np.testing.assert_allclose(
            np.asarray(leaf_sh, jnp.float32),
            np.asarray(leaf_ref, jnp.float32), atol=5e-2, rtol=5e-3)


# ---------------------------------------------------------------------------
# MoE all-to-all dispatch
# ---------------------------------------------------------------------------
@needs_mesh
def test_moe_a2a_matches_block_dispatch():
    """shard_map all-to-all token exchange ≡ the capacity-scatter path with
    block-local ranking (blocks = token shards), forward and grads."""
    from repro.models import moe

    spec = moe.MoESpec(n_experts=8, n_experts_padded=8, top_k=2, d_model=64,
                       d_ff=128, capacity_factor=8.0, dispatch_blocks=8)
    params = moe.init_moe(KEY, spec, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (4, 32, 64))
    y_ref, aux_ref = moe.moe_mlp(params, x, spec)

    mesh = _mesh()
    spec_a2a = dataclasses.replace(spec, a2a_axis="model")
    with mesh:
        y, aux = moe.moe_mlp(params, x, spec_a2a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), atol=1e-6)

    def loss(params, x, s):
        with mesh:
            y, aux = moe.moe_mlp(params, x, s)
        return (y ** 2).sum() + aux

    g = jax.grad(loss)(params, x, spec_a2a)
    g_ref = jax.grad(loss)(params, x, spec)
    for k in ("w_gate", "w_up", "w_down", "router"):
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(g_ref[k]),
                                   atol=1e-3, rtol=1e-3, err_msg=k)


@needs_mesh
def test_moe_a2a_falls_back_when_shapes_dont_divide():
    from repro.models import moe

    spec = moe.MoESpec(n_experts=6, n_experts_padded=6, top_k=2, d_model=64,
                       d_ff=128, a2a_axis="model")   # 6 % 2 == 0 but t odd
    params = moe.init_moe(KEY, spec, jnp.float32)
    x = jax.random.normal(KEY, (1, 17, 64))          # 17 tokens: no divide
    with _mesh():
        y, aux = moe.moe_mlp(params, x, spec)
    assert np.all(np.isfinite(np.asarray(y)))


# ---------------------------------------------------------------------------
# sharding-rule plans
# ---------------------------------------------------------------------------
@needs_mesh
def test_packed_matmul_plans_follow_param_specs():
    from repro import configs
    from repro.runtime import sharding as shard_mod

    mesh = _mesh()
    cfg = configs.get_config("llama3.2-1b")
    _, up = _packed((256, 512), seed=9)     # w_up: N-sharded → col plan
    _, down = _packed((512, 256), seed=10)  # w_down: K-sharded → row plan
    plans = shard_mod.packed_matmul_plans(
        {"blocks": {"mlp": {"w_up": up, "w_down": down}}}, cfg, mesh)
    assert plans[".blocks.mlp.w_up"].col_axis == "model"
    assert plans[".blocks.mlp.w_down"].row_axis == "model"
    for plan in plans.values():
        assert plan.batch_axes == ("data",)


@needs_mesh
def test_planner_attaches_spmd_plans_and_dispatch_uses_them(
        interpret_backend, tmp_path):
    """build_plan(mesh=) records each leaf's resident-sharding SpmdPlan;
    under use_plan a bare sod.apply runs shard_map-wrapped under exactly
    that plan — including after a JSON round trip."""
    from repro import configs
    from repro.core import plan as plan_mod
    from repro.core import sod
    from repro.core.plan import ModelPlan
    from repro.core.sod import SoDConfig, sodify_params
    from repro.runtime import planner

    mesh = _mesh()
    cfg = configs.get_config("llama3.2-1b")
    sodc = SoDConfig(mode="tiled_csc", density=0.3, min_dim=128)
    wu = pruning.random_sparse(jax.random.fold_in(KEY, 21), (256, 512), 0.3)
    wd = pruning.random_sparse(jax.random.fold_in(KEY, 22), (512, 256), 0.3)
    params = {"blocks": {"mlp": {"w_up": wu, "w_down": wd}}}
    plan = planner.build_plan(params, sodc, cfg=cfg, mesh=mesh,
                              m_values=(48,))
    assert plan.mesh == spmd.mesh_key(mesh)
    assert plan.get(".blocks.mlp.w_up").spmd["col_axis"] == "model"
    assert plan.get(".blocks.mlp.w_down").spmd["row_axis"] == "model"
    # round trip: the loaded plan is the plan
    loaded = ModelPlan.load(plan.save(tmp_path / "plan.json"))
    assert loaded.entries == plan.entries and loaded.mesh == plan.mesh

    packed = sodify_params(params, sodc, plan=loaded)
    x = jax.random.normal(jax.random.fold_in(KEY, 23), (48, 256),
                          jnp.float32)
    with mesh, plan_mod.use_plan(loaded), \
            registry.record_dispatches() as log:
        y = jax.jit(lambda x, w: sod.apply(x, w))(
            x, packed["blocks"]["mlp"]["w_up"])
    assert log and "col=model" in log[-1]["key"].mesh
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(x @ packed["blocks"]["mlp"]["w_up"].to_dense()),
        atol=2e-2)


# ---------------------------------------------------------------------------
# acceptance smoke (always runs: subprocess forces its own devices)
# ---------------------------------------------------------------------------
def test_spmd_acceptance_subprocess():
    """ISSUE 2 acceptance, isolated from this process's device count: on a
    fake 8-device mesh a pjit-sharded sod_matmul dispatches a
    shard_map-wrapped Pallas impl (not the XLA oracle), and forward +
    jax.grad match the dense reference."""
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import tempfile
os.environ['REPRO_TUNING_CACHE'] = os.path.join(
    tempfile.mkdtemp(), 'cache.json')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import pruning
from repro.core.formats import pack_tiled_csc
from repro.kernels import ops, registry
mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ('data', 'model'))
w = pruning.random_sparse(jax.random.PRNGKey(0), (256, 512), 0.3)
p = pack_tiled_csc(w)
x = jax.random.normal(jax.random.PRNGKey(1), (48, 256))
registry.set_backend_override('interpret')
def loss(x, p):
    with mesh:
        return (jax.jit(lambda x, p: ops.sod_matmul(x, p))(x, p) ** 2).sum()
with registry.record_dispatches() as log:
    gx, gp = jax.grad(loss, argnums=(0, 1), allow_int=True)(x, p)
assert log and all(r['impl'] == 'pallas_fused' and r['key'].mesh
                   for r in log), log
gx_ref, gw_ref = jax.grad(lambda x, w: ((x @ w) ** 2).sum(),
                          argnums=(0, 1))(x, w)
assert np.allclose(np.asarray(gx), np.asarray(gx_ref), atol=2e-2)
pad = np.asarray(p.rows) < 0
assert np.all(np.asarray(gp.vals)[pad] == 0)
print('OK')
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
