"""Mixture-of-Experts MLP with top-k routing, shared experts, EP sharding.

Covers the two assigned MoE archs:
  * qwen2-moe-a2.7b — 60 routed experts top-4 + shared expert (+ gate)
  * granite-moe-1b  — 32 routed experts top-8, no shared expert

Dispatch is capacity-based (scatter → batched expert einsum → combine) so the
expert dimension shards cleanly on the ``model`` axis (expert parallelism)
and HLO FLOPs reflect *active* experts, not a dense-all-experts product.
Experts are ceil-padded to the EP axis size; the router masks padding.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

try:  # moved out of experimental in newer jax
    from jax.experimental.shard_map import shard_map
except ImportError:  # pragma: no cover - version dependent
    from jax import shard_map

from repro.core import sod
from repro.models import layers

Params = dict[str, Any]


def _maybe_constrain(x: jax.Array, *axes):
    """Sharding constraint when tracing under a mesh; no-op otherwise.

    GSPMD won't propagate data-sharding through the computed-index dispatch
    scatter (it conservatively all-reduces the whole capacity buffer — §Perf
    B2, refuted); the explicit constraint pins E to the model axis and the
    block dim to the data axes (B3)."""
    try:
        from jax.interpreters import pxla
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = pxla.thread_resources.env.physical_mesh
        if mesh.empty:
            return x
        names = set(mesh.axis_names)
        fixed = []
        for a in axes:
            if a is None:
                fixed.append(None)
            elif a == "data":
                dp = tuple(n for n in ("pod", "data") if n in names)
                fixed.append(dp if len(dp) > 1 else (dp[0] if dp else None))
            else:
                fixed.append(a if a in names else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*fixed)))
    except Exception:
        return x


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int          # real experts
    n_experts_padded: int   # ceil-padded to EP axis
    top_k: int
    d_model: int
    d_ff: int               # per-expert hidden
    n_shared: int = 0       # shared experts (always-on)
    d_shared_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    act: str = "silu"
    # Rank/dispatch within this many contiguous token blocks.  When blocks
    # align with the data-parallel sharding of the token dim, the dispatch
    # scatter is shard-local — no capacity-buffer all-reduce over the data
    # axis (EXPERIMENTS.md §Perf B2).  1 = global dispatch.
    dispatch_blocks: int = 1
    # Mesh axis for the shard_map all-to-all token exchange (the §Perf B3
    # fix): tokens shard over (data axes × this axis), each shard ranks its
    # block locally and trades per-expert capacity buffers with its EP
    # peers — only routed tokens cross the links, never the full capacity
    # buffer.  None (default) keeps the GSPMD capacity-scatter dispatch;
    # the a2a path engages when a mesh with this axis is active and shapes
    # divide, and falls back silently otherwise.
    a2a_axis: str | None = None

    def capacity(self, n_tokens: int) -> int:
        c = int(n_tokens * self.top_k / max(self.n_experts, 1)
                * self.capacity_factor)
        return max((c + 127) // 128 * 128, 128)


def pad_experts(n_experts: int, ep_axis: int = 16) -> int:
    return (n_experts + ep_axis - 1) // ep_axis * ep_axis


def init_moe(key, spec: MoESpec, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 5)
    e, d, f = spec.n_experts_padded, spec.d_model, spec.d_ff

    def expert_init(k, d_in, d_out):
        scale = (1.0 / d_in) ** 0.5
        return (jax.random.normal(k, (e, d_in, d_out), jnp.float32) * scale).astype(dtype)

    params: Params = {
        "router": layers.dense_init(ks[0], d, e, jnp.float32),
        "w_gate": expert_init(ks[1], d, f),
        "w_up": expert_init(ks[2], d, f),
        "w_down": expert_init(ks[3], f, d),
    }
    if spec.n_shared:
        params["shared"] = layers.init_mlp(
            ks[4], d, spec.d_shared_ff or spec.d_ff * spec.n_shared, dtype
        )
        params["shared_gate"] = layers.dense_init(
            jax.random.fold_in(ks[4], 1), d, 1, jnp.float32
        )
    return params


def _rank_in_expert(assign: jax.Array, e: int) -> jax.Array:
    """First-come slot rank of each assignment within its expert.

    Stable argsort — O(N log N), no (T·K × E) one-hot, same first-come slot
    semantics as a running per-expert counter (§Perf B1).
    """
    order = jnp.argsort(assign, stable=True)
    sorted_e = assign[order]
    hist = jnp.zeros((e,), jnp.int32).at[assign].add(1)
    starts = jnp.cumsum(hist) - hist                          # (E,) tiny
    rank = jnp.arange(assign.shape[0], dtype=jnp.int32) - starts[sorted_e]
    return jnp.zeros_like(assign).at[order].set(rank)


def _a2a_dispatch(params: Params, xt: jax.Array, gate_vals: jax.Array,
                  expert_ids: jax.Array, spec: MoESpec):
    """shard_map all-to-all token exchange (the §Perf B3 fix), or None.

    Tokens shard over (data axes × ``spec.a2a_axis``); each shard ranks its
    contiguous block locally (same semantics as ``dispatch_blocks`` = the
    number of token shards), scatters its tokens into a per-expert capacity
    buffer, and ``all_to_all`` over the EP axis hands every expert owner
    exactly the routed tokens — the giant (E, NB, C, D) capacity buffer is
    never materialized globally and no GSPMD resharding of ``src`` happens.
    Expert weights stay resident sharded on the EP axis; their cotangents
    psum over the data axes via the shard_map transpose.
    """
    from repro.runtime import spmd  # deferred: models layer under runtime

    mesh = spmd.active_mesh()
    ep_ax = spec.a2a_axis
    if mesh is None or ep_ax not in mesh.axis_names or spmd.in_spmd_body():
        return None
    t, d = xt.shape
    e = spec.n_experts_padded
    ep = mesh.shape[ep_ax]
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    if ep_ax in dp:
        return None                      # EP axis must be distinct from dp
    n_tok_shards = ep
    for a in dp:
        n_tok_shards *= mesh.shape[a]
    if e % ep or t % n_tok_shards:
        return None                      # shapes don't divide: fall back
    t_l = t // n_tok_shards
    cap = spec.capacity(t_l)
    k = spec.top_k
    e_per = e // ep
    tok_axes = dp + (ep_ax,)

    def body(xt_l, gate_l, eid_l, wg_l, wu_l, wd_l):
        assign = eid_l.reshape(-1)                       # (A,) A = t_l·K
        slot = _rank_in_expert(assign, e)
        keep = slot < cap
        # local per-expert capacity buffer, drop bin at cap
        src = jnp.repeat(xt_l[:, None, :], k, axis=1).reshape(-1, d)
        buf = jnp.zeros((e, cap + 1, d), xt_l.dtype)
        buf = buf.at[assign, jnp.where(keep, slot, cap)].add(src,
                                                             mode="drop")
        buf = buf[:, :cap]                               # (E, C, D)
        # trade expert slices: every EP peer receives, for its e_per local
        # experts, the capacity buffers of all ep sources
        recv = jax.lax.all_to_all(buf, ep_ax, split_axis=0, concat_axis=1,
                                  tiled=True)            # (E/ep, ep·C, D)
        h_gate = jnp.einsum("ecd,edf->ecf", recv, wg_l,
                            preferred_element_type=jnp.float32
                            ).astype(xt_l.dtype)
        h_up = jnp.einsum("ecd,edf->ecf", recv, wu_l,
                          preferred_element_type=jnp.float32
                          ).astype(xt_l.dtype)
        h = layers.activate(h_gate, spec.act) * h_up
        out_e = jnp.einsum("ecf,efd->ecd", h, wd_l,
                           preferred_element_type=jnp.float32
                           ).astype(xt_l.dtype)          # (E/ep, ep·C, D)
        # route results back to their source shards
        back = jax.lax.all_to_all(out_e, ep_ax, split_axis=1, concat_axis=0,
                                  tiled=True)            # (E, C, D)
        gathered = back[assign, jnp.clip(slot, 0, cap - 1)]
        gathered = jnp.where(keep[:, None], gathered, 0)
        weights = (gate_l.reshape(-1) * keep).astype(xt_l.dtype)
        return jnp.sum((gathered * weights[:, None]).reshape(t_l, k, d),
                       axis=1)

    tok_spec = P(tok_axes, None)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P(ep_ax, None, None), P(ep_ax, None, None),
                  P(ep_ax, None, None)),
        out_specs=tok_spec,
        check_rep=False)
    return fn(xt, gate_vals, expert_ids,
              params["w_gate"], params["w_up"], params["w_down"])


def moe_mlp(params: Params, x: jax.Array, spec: MoESpec, plans=None):
    """x (B, S, D) → (B, S, D), plus router aux loss.

    ``plans`` maps the *shared* expert's projection names to their
    :class:`repro.core.plan.PackPlan` (routed experts are stacked on the EP
    dim and dispatch through the batched einsum path, which plans don't
    cover).
    """
    b, s, d = x.shape
    t = b * s
    e = spec.n_experts_padded
    xt = x.reshape(t, d)

    logits = jnp.dot(xt, params["router"].astype(xt.dtype),
                     preferred_element_type=jnp.float32)
    if spec.n_experts_padded > spec.n_experts:
        pad_mask = jnp.arange(spec.n_experts_padded) >= spec.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, spec.top_k)  # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    combined = None
    if spec.a2a_axis is not None:
        combined = _a2a_dispatch(params, xt, gate_vals, expert_ids, spec)

    if combined is None:
        # ---- capacity-based dispatch (block-local, sort-based ranking) ----
        # B1: rank assignments within their expert via a stable argsort.
        # B2: ranking/scatter happen independently per token *block*; blocks
        #     align with the data sharding so the dispatch scatter is local.
        nb = spec.dispatch_blocks if t % spec.dispatch_blocks == 0 else 1
        tb = t // nb
        cap = spec.capacity(tb)
        a_blk = expert_ids.reshape(nb, tb * spec.top_k)       # (NB, A)
        slot = jax.vmap(lambda a: _rank_in_expert(a, e))(a_blk) \
            .reshape(t, spec.top_k)
        keep = slot < cap
        # scatter tokens into (E, NB, C, D); NB rides the token sharding
        flat_e = expert_ids.reshape(-1)
        flat_b = jnp.repeat(jnp.arange(nb, dtype=jnp.int32), tb * spec.top_k)
        flat_slot = jnp.where(keep, slot, cap).reshape(-1)    # cap = drop bin
        dispatched = jnp.zeros((e, nb, cap + 1, d), xt.dtype)
        src = jnp.repeat(xt[:, None, :], spec.top_k, axis=1).reshape(-1, d)
        dispatched = dispatched.at[flat_e, flat_b, flat_slot].add(
            src, mode="drop")
        # NOTE: forcing P('model','data',·,·) here makes GSPMD reshard the
        # giant src instead (16× more traffic — §Perf B3, refuted).  The
        # real fix is the shard_map all-to-all exchange above
        # (spec.a2a_axis); this path remains for meshless runs and
        # non-dividing shapes.
        dispatched = dispatched[:, :, :cap]                   # (E, NB, C, D)

        # ---- batched expert MLP (E shards on "model", NB on data) --------
        h_gate = jnp.einsum("ebcd,edf->ebcf", dispatched, params["w_gate"],
                            preferred_element_type=jnp.float32
                            ).astype(xt.dtype)
        h_up = jnp.einsum("ebcd,edf->ebcf", dispatched, params["w_up"],
                          preferred_element_type=jnp.float32).astype(xt.dtype)
        h = layers.activate(h_gate, spec.act) * h_up
        out_e = jnp.einsum("ebcf,efd->ebcd", h, params["w_down"],
                           preferred_element_type=jnp.float32
                           ).astype(xt.dtype)

        # ---- combine ------------------------------------------------------
        gathered = out_e[flat_e, flat_b, jnp.clip(flat_slot, 0, cap - 1)]
        gathered = jnp.where(keep.reshape(-1, 1), gathered, 0)
        weights = (gate_vals * keep).reshape(-1, 1).astype(xt.dtype)
        combined = jnp.sum(
            (gathered * weights).reshape(t, spec.top_k, d), axis=1
        )

    if "shared" in params:
        sg = jax.nn.sigmoid(
            jnp.dot(xt, params["shared_gate"].astype(xt.dtype))
        ).astype(xt.dtype)
        combined = combined + sg * layers.mlp(params["shared"], xt, spec.act,
                                              plans=plans)

    # ---- load-balance aux loss (Switch-style) ------------------------------
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids[:, 0], e), axis=0) / t
    ) * e
    frac = jnp.mean(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32), axis=(0, 1))
    aux = spec.router_aux_weight * jnp.sum(frac * me) * e

    return combined.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# held experts: one chip's share of an expert-parallel layer, dropless
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HeldSpec:
    """An expert layer told which of its experts it holds.  The router
    scores all ``n_experts`` and picks ``top_k``, each weighted by its
    softmax score, unnormalised (DeepSeek-V2's routing); only the ``held``
    experts' part of the result is computed, plus the shared experts
    (always on, ungated), so the shares of every chip add up to the whole
    layer with the shared experts counted once."""

    n_experts: int                  # the router's width: the whole layer
    held: tuple[int, ...]           # expert ids this chip holds
    top_k: int
    d_model: int
    d_ff: int                       # per-expert hidden
    d_shared_ff: int = 0            # the shared experts' hidden (0: none)
    act: str = "silu"


def init_held_moe(key, spec: HeldSpec, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 5)
    e, d, f = len(spec.held), spec.d_model, spec.d_ff

    def expert_init(k, d_in, d_out):
        return jax.vmap(lambda kk: layers.dense_init(kk, d_in, d_out, dtype))(
            jax.random.split(k, e))

    params: Params = {
        "router": layers.dense_init(ks[0], d, spec.n_experts, jnp.float32),
        "w_gate": expert_init(ks[1], d, f),
        "w_up": expert_init(ks[2], d, f),
        "w_down": expert_init(ks[3], f, d),
    }
    if spec.d_shared_ff:
        params["shared"] = layers.init_mlp(ks[4], d, spec.d_shared_ff, dtype)
    return params


def held_route(router: jax.Array, xt: jax.Array, spec: HeldSpec):
    """Each token's weight on each held expert, (T, E_held) float32 (0
    where the token did not pick it), and whether it picked it.  Scores
    are a float32 softmax over every expert at full precision."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        vals, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                  spec.top_k)
        hit = ids[:, :, None] == jnp.asarray(spec.held, jnp.int32)
        weights = jnp.sum(jnp.where(hit, vals[:, :, None], 0.0), axis=1)
    return weights, jnp.any(hit, axis=1)


def held_moe(params: Params, x: jax.Array, spec: HeldSpec):
    """x (B, S, D) → (the held experts' part of the layer plus the shared
    experts', (B, S, D); the rows routed to a held expert, summed over
    the held experts).

    Dropless and batch-independent: each held expert multiplies every row
    of the step (one packed matmul per expert matrix, so every one runs
    the Sparse-on-Dense kernel) and a row keeps the product at its routing
    weight, which is exactly 0 where it did not pick that expert.  No
    capacity, so no row is ever dropped and a row's result does not depend
    on the other rows of its batch."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    weights, routed = held_route(params["router"], xt, spec)
    out = jnp.zeros(xt.shape, jnp.float32)
    with jax.named_scope("moe.held_experts"):
        for e in range(len(spec.held)):
            w = {k: jax.tree_util.tree_map(lambda a: a[e], params[k])
                 for k in ("w_gate", "w_up", "w_down")}
            y = layers.mlp(w, xt, spec.act)
            out = out + weights[:, e:e + 1] * y.astype(jnp.float32)
    if "shared" in params:
        out = out + layers.mlp(params["shared"], xt, spec.act).astype(
            jnp.float32)
    rows = jnp.sum(routed, dtype=jnp.int32)
    return out.astype(x.dtype).reshape(b, s, d), rows
