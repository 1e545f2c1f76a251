"""Jittable train / prefill / decode step builders.

Every builder takes an optional ``mesh``: when given, the step body traces
inside ``with mesh:``, so the SPMD kernel routing
(:mod:`repro.runtime.spmd`) sees the mesh even if the caller jits the step
without an enclosing mesh context — packed matmuls then dispatch
shard_map-wrapped Pallas kernels instead of falling back to the XLA oracle.

Every builder also takes an optional ``plan`` (a
:class:`repro.core.plan.ModelPlan`): the step body traces inside
:func:`repro.core.plan.use_plan`, so each packed matmul dispatches with its
layer's :class:`~repro.core.plan.PackPlan` (impl hint, tuned dispatch
params, per-layer SPMD partition plan) instead of rediscovering a choice
per call.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.plan import use_plan
from repro.launch.mesh import mesh_context
from repro.models.model import LM
from repro.optim.adamw import AdamW

Params = Any


def make_train_step(model: LM, optimizer: AdamW, mesh=None, plan=None):
    def train_step(params: Params, opt_state: Params, batch: Params):
        def loss_fn(p):
            return model.loss(p, batch)

        with mesh_context(mesh), use_plan(plan):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True, allow_int=True)(params)
            params, opt_state, opt_metrics = optimizer.update(
                params, grads, opt_state)
        return params, opt_state, {**metrics, **opt_metrics}

    return train_step


def make_loss_and_grads(model: LM, mesh=None, plan=None):
    def loss_and_grads(params: Params, batch: Params):
        with mesh_context(mesh), use_plan(plan):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: model.loss(p, batch), has_aux=True, allow_int=True
            )(params)
        return loss, metrics, grads

    return loss_and_grads


def make_prefill_step(model: LM, mesh=None, plan=None):
    def prefill_step(params: Params, batch: Params):
        with mesh_context(mesh), use_plan(plan):
            last_logits, cache = model.prefill(params, batch)
        next_tokens = jnp.argmax(last_logits, axis=-1)
        return next_tokens, cache

    return prefill_step


def make_decode_step(model: LM, greedy: bool = True, mesh=None, plan=None):
    def decode_step(params: Params, cache: Params, tokens, pos):
        with mesh_context(mesh), use_plan(plan):
            logits, cache = model.decode_step(params, cache, tokens, pos)
        next_tokens = jnp.argmax(logits, axis=-1)
        return next_tokens, logits, cache

    return decode_step


def make_prefill_full(model: LM, mesh=None, plan=None):
    """Prefill returning *all* positions' logits (not just the last).

    The serving engine pads prompts to a page-aligned bucket before the
    fused prefill, so the last *real* token's logits live at ``len - 1``
    rather than ``-1`` — the engine slices them out on the host.
    """
    def prefill_full(params: Params, batch: Params):
        with mesh_context(mesh), use_plan(plan):
            logits, _, cache = model.apply(params, batch, want_cache=True)
        return logits, cache

    return prefill_full


def make_chunked_prefill_step(model: LM, mesh=None, plan=None):
    """One chunk of prompt prefill into the paged KV pool (continuous
    batching): admission splits a prompt into fixed-token chunks that
    interleave with running decode steps, so a long prompt never freezes
    the batch.  ``tokens`` (B, C) covers positions [start, start+C);
    logits for every chunk position come back so the engine can slice the
    last real prompt token's row out on the host."""
    def chunked_prefill_step(params: Params, pool: Params, block_tables,
                             tokens, start, valid_len):
        with mesh_context(mesh), use_plan(plan):
            logits, pool = model.prefill_chunk(
                params, pool, block_tables, tokens, start, valid_len)
        return logits, pool

    return chunked_prefill_step


def make_verify_step(model: LM, mesh=None, plan=None):
    """Speculative-window verification step (continuous batching): every
    engine slot scores its committed token + k draft proposals in one
    batched pass.  ``tokens`` is (B, C=k+1) covering cache positions
    [start[b], start[b]+C) per row; returns per-position greedy tokens,
    the raw logits, and the updated pool.  Row ``(b, i)`` of the greedy
    tokens is bitwise what the sequential paged decode step would emit at
    that position — the engine's accept rule depends on it."""
    def verify_step(params: Params, pool: Params, block_tables,
                    tokens, start, valid_len):
        with mesh_context(mesh), use_plan(plan):
            logits, pool = model.verify_chunk(
                params, pool, block_tables, tokens, start, valid_len)
        next_tokens = jnp.argmax(logits, axis=-1)
        return next_tokens, logits, pool

    return verify_step


def make_paged_decode_step(model: LM, mesh=None, plan=None):
    """Ragged decode step over the paged KV pool (continuous batching):
    every engine slot decodes at its own ``pos`` against its own pages.
    ``valid_len`` (optional, (B,)) is the per-row write cutoff the engine
    uses to batch decoding rows with prefilling/idle ones — rows at or
    beyond their cutoff write to the trash page.  Returns (next tokens,
    logits, pool), then whatever counters the model's step returns (a
    model with held experts: the rows routed to them)."""
    def paged_decode_step(params: Params, pool: Params, block_tables,
                          tokens, pos, valid_len=None):
        with mesh_context(mesh), use_plan(plan):
            logits, pool, *counters = model.paged_decode_step(
                params, pool, block_tables, tokens, pos,
                valid_len=valid_len)
        next_tokens = jnp.argmax(logits, axis=-1)
        return (next_tokens, logits, pool, *counters)

    return paged_decode_step
