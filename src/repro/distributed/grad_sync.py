"""Manual data-parallel training step with *compressed* cross-shard
gradient synchronization (int8 wire + error feedback) — the
distributed-optimization trick for the slow inter-pod link.

Under GSPMD the gradient all-reduce is implicit (and fp32/bf16 on the
wire); this explicit shard_map variant trades that for a 4x smaller
payload on the designated axis, with EF-SGD convergence (tests verify
parity with uncompressed sync on a quadratic and an LM smoke model).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim.compression import ErrorFeedback


def pmean_grads(grads, axis_name):
    """Cross-shard gradient mean — the uncompressed synchronization used
    by the sharded fleet backend's refine step (the loss is pre-scaled by
    the shard count, so the pmean reconstructs the global psum; see
    ``core.fleet_refiner.make_fleet_loss``)."""
    return jax.tree.map(lambda g: jax.lax.pmean(g, axis_name), grads)


def psum_grads(grads, axis_name):
    """Cross-shard gradient sum, for losses that already carry global
    normalization."""
    return jax.tree.map(lambda g: jax.lax.psum(g, axis_name), grads)


def make_compressed_dp_step(mesh, loss_fn, opt_update, *, axis="data",
                            lr=1e-3, compress=True, opt_kwargs=None):
    """loss_fn(params, batch) -> scalar;  batch sharded over ``axis``.

    Returns step(params, opt_state, ef_state, batch) with params replicated
    and gradients synchronized via int8 psum + error feedback."""
    opt_kwargs = opt_kwargs or {}

    def local_step(params, opt_state, ef, batch):
        grads = jax.grad(loss_fn)(params, batch)
        if compress:
            grads, ef = ErrorFeedback.apply(grads, ef, axis_name=axis)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
        params, opt_state = opt_update(params, grads, opt_state, lr=lr,
                                       **opt_kwargs)
        return params, opt_state, ef

    def batch_spec(batch):
        return jax.tree.map(lambda _: P(axis), batch)

    jitted = {}   # one jitted step per batch tree structure — rebuilding
                  # per call would retrace/recompile every training step

    def step(params, opt_state, ef, batch):
        structure = jax.tree.structure(batch)
        if structure not in jitted:
            jitted[structure] = jax.jit(jax.shard_map(
                local_step, mesh=mesh,
                in_specs=(P(), P(), P(), batch_spec(batch)),
                out_specs=(P(), P(), P()),
                check_vma=False))
        return jitted[structure](params, opt_state, ef, batch)

    return step


def ef_init(params):
    return ErrorFeedback.init(params)
