"""Mixture-of-Experts with top-k routing.

Two execution paths:

* ``moe_reference`` — every expert on every token (einsum over the full
  expert dim).  Exact, no capacity drops; used by smoke configs, unit tests
  and as the oracle for the EP path.

* ``moe_ep`` — expert parallelism via ``shard_map``: experts sharded over
  the 'model' mesh axis, tokens sequence-sharded over 'model', dispatched
  with a fixed-capacity all-to-all (GShard-style dropping), grouped batched
  matmul per local expert, and a return all-to-all.  This is the scalable
  path used by the kimi-k2 / arctic dry-runs.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd
from repro.models.common import activation, dense_init


def init_moe(key, moe_cfg, d_model, *, dtype=jnp.float32):
    E, ff = moe_cfg.n_experts, moe_cfg.d_ff_expert
    ks = jax.random.split(key, 4)
    params, axes = {}, {}
    params["router"], axes["router"] = dense_init(
        ks[0], (d_model, E), ("router", "router"), dtype=jnp.float32)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(ff)
    def expert_w(k, shape, scale):
        return scale * jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                   jnp.float32).astype(dtype)
    params["w_up"] = expert_w(ks[1], (E, d_model, ff), s_in)
    axes["w_up"] = ("experts", "embed", "expert_mlp")
    if moe_cfg.gated:
        params["w_gate"] = expert_w(ks[2], (E, d_model, ff), s_in)
        axes["w_gate"] = ("experts", "embed", "expert_mlp")
    params["w_down"] = expert_w(ks[3], (E, ff, d_model), s_out)
    axes["w_down"] = ("experts", "expert_mlp", "embed")
    return params, axes


def _router(p, moe_cfg, x2d):
    """x2d: (T, d) -> (top_p, top_e, probs).  Softmax-then-topk-renorm."""
    logits = x2d.astype(jnp.float32) @ p["router"]["w"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe_cfg.top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    return top_p, top_e, probs


def _aux_loss(moe_cfg, probs, top_e):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    E = moe_cfg.n_experts
    assign = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(axis=1)  # (T, E)
    f = assign.mean(axis=0) / moe_cfg.top_k * E
    P = probs.mean(axis=0)
    return jnp.sum(f * P)


def _expert_ffn(moe_cfg, w_up, w_gate, w_down, xb):
    """xb: (E_local, C, d) -> (E_local, C, d)."""
    fn = activation(moe_cfg.act)
    up = jnp.einsum("ecd,edf->ecf", xb, w_up.astype(xb.dtype))
    if w_gate is not None:
        h = fn(jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(xb.dtype))) * up
    else:
        h = fn(up)
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(xb.dtype))


# ---------------------------------------------------------------------------
# Reference path (tiny configs, oracle)
# ---------------------------------------------------------------------------

def moe_reference(p, moe_cfg, x):
    """x: (B, S, d).  Computes all experts on all tokens — exact."""
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    top_p, top_e, probs = _router(p, moe_cfg, x2d)
    fn = activation(moe_cfg.act)
    up = jnp.einsum("td,edf->tef", x2d, p["w_up"].astype(x.dtype))
    if "w_gate" in p:
        h = fn(jnp.einsum("td,edf->tef", x2d, p["w_gate"].astype(x.dtype))) * up
    else:
        h = fn(up)
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(x.dtype))  # (T,E,d)
    w_full = jnp.zeros((x2d.shape[0], moe_cfg.n_experts), jnp.float32)
    w_full = w_full.at[jnp.arange(x2d.shape[0])[:, None], top_e].add(top_p)
    y = jnp.einsum("te,ted->td", w_full.astype(x.dtype), y_all)
    aux = _aux_loss(moe_cfg, probs, top_e)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel path
# ---------------------------------------------------------------------------

def _local_moe(moe_cfg, R, E_local, cap_factor, mesh_axes, x_local, router_w,
               w_up, w_gate, w_down):
    """Per-device body under shard_map.

    x_local: (B_l, S_l, d) — tokens owned by this device (seq split over
    'model', batch split over data axes).  Experts [rank*E_local, ...) live
    here as w_* blocks.
    """
    B_l, S_l, d = x_local.shape
    T = B_l * S_l
    k = moe_cfg.top_k
    x2d = x_local.reshape(T, d)
    top_p, top_e, probs = _router({"router": {"w": router_w}}, moe_cfg, x2d)
    # globally exact load-balance loss: pmean the per-expert fractions f_e
    # and mean probs P_e across shards BEFORE taking the product (a mean of
    # per-shard products is a biased estimator).
    E = moe_cfg.n_experts
    assign = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(axis=1)
    f = jax.lax.pmean(assign.mean(axis=0), mesh_axes) / moe_cfg.top_k * E
    Pm = jax.lax.pmean(probs.mean(axis=0), mesh_axes)
    aux = jnp.sum(f * Pm)

    copies = T * k
    CAP = int(math.ceil(copies / R * cap_factor))
    ECAP = int(math.ceil(R * CAP / E_local * cap_factor))

    eid = top_e.reshape(-1)                      # (T*k,)
    gate = top_p.reshape(-1).astype(x2d.dtype)
    src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    dst = eid // E_local                          # destination model-rank

    onehot_dst = (dst[:, None] == jnp.arange(R)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot_dst, axis=0) - 1
    pos = jnp.sum(pos * onehot_dst, axis=-1)
    keep = pos < CAP
    slot = jnp.where(keep, dst * CAP + pos, R * CAP)  # overflow -> dump row

    send_x = jnp.zeros((R * CAP + 1, d), x2d.dtype).at[slot].set(x2d[src])
    send_le = jnp.full((R * CAP + 1,), -1, jnp.int32).at[slot].set(
        (eid % E_local).astype(jnp.int32))
    slot_src = jnp.full((R * CAP + 1,), -1, jnp.int32).at[slot].set(src)
    slot_w = jnp.zeros((R * CAP + 1,), x2d.dtype).at[slot].set(gate)

    recv_x = jax.lax.all_to_all(
        send_x[: R * CAP].reshape(R, CAP, d), "model", 0, 0).reshape(R * CAP, d)
    recv_le = jax.lax.all_to_all(
        send_le[: R * CAP].reshape(R, CAP), "model", 0, 0).reshape(R * CAP)

    onehot_e = (recv_le[:, None] == jnp.arange(E_local)[None, :]).astype(jnp.int32)
    epos = jnp.cumsum(onehot_e, axis=0) - 1
    epos = jnp.sum(epos * onehot_e, axis=-1)
    ekeep = (recv_le >= 0) & (epos < ECAP)
    eslot = jnp.where(ekeep, recv_le * ECAP + epos, E_local * ECAP)

    ebuf = jnp.zeros((E_local * ECAP + 1, d), x2d.dtype).at[eslot].set(recv_x)
    ebuf = ebuf[:-1].reshape(E_local, ECAP, d)
    ybuf = _expert_ffn(moe_cfg, w_up, w_gate, w_down, ebuf)
    ypad = jnp.concatenate(
        [ybuf.reshape(E_local * ECAP, d), jnp.zeros((1, d), ybuf.dtype)], 0)
    ret = jnp.where(ekeep[:, None], ypad[eslot], 0)

    back = jax.lax.all_to_all(
        ret.reshape(R, CAP, d), "model", 0, 0).reshape(R * CAP, d)
    out_src = jnp.where(slot_src[: R * CAP] >= 0, slot_src[: R * CAP], T)
    out = jnp.zeros((T + 1, d), x2d.dtype).at[out_src].add(
        slot_w[: R * CAP, None] * back)
    return out[:T].reshape(B_l, S_l, d), aux


def _local_moe_replicated(moe_cfg, R, E_local, cap_factor, mesh_axes,
                          x_local, router_w, w_up, w_gate, w_down):
    """EP without token dispatch — for decode-style tiny token counts.

    Tokens are replicated over 'model'; each rank computes only its local
    experts' contributions and the outputs are psum'd.  No all-to-all."""
    B_l, S_l, d = x_local.shape
    T = B_l * S_l
    k = moe_cfg.top_k
    x2d = x_local.reshape(T, d)
    top_p, top_e, probs = _router({"router": {"w": router_w}}, moe_cfg, x2d)
    E = moe_cfg.n_experts
    assign = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(axis=1)
    f = jax.lax.pmean(assign.mean(axis=0), mesh_axes) / moe_cfg.top_k * E
    Pm = jax.lax.pmean(probs.mean(axis=0), mesh_axes)
    aux = jnp.sum(f * Pm)

    rank = jax.lax.axis_index("model")
    eid = top_e.reshape(-1)
    gate = top_p.reshape(-1).astype(x2d.dtype)
    src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    le = eid - rank * E_local                      # local expert id
    mine = (le >= 0) & (le < E_local)
    ECAP = int(math.ceil(T * k / E_local * cap_factor))

    onehot_e = (jnp.where(mine, le, -1)[:, None]
                == jnp.arange(E_local)[None, :]).astype(jnp.int32)
    epos = jnp.cumsum(onehot_e, axis=0) - 1
    epos = jnp.sum(epos * onehot_e, axis=-1)
    keep = mine & (epos < ECAP)
    eslot = jnp.where(keep, le * ECAP + epos, E_local * ECAP)
    ebuf = jnp.zeros((E_local * ECAP + 1, d), x2d.dtype).at[eslot].set(
        x2d[src])
    ebuf = ebuf[:-1].reshape(E_local, ECAP, d)
    ybuf = _expert_ffn(moe_cfg, w_up, w_gate, w_down, ebuf)
    ypad = jnp.concatenate(
        [ybuf.reshape(E_local * ECAP, d), jnp.zeros((1, d), ybuf.dtype)], 0)
    contrib = jnp.where(keep[:, None], ypad[jnp.minimum(eslot,
                                                        E_local * ECAP)], 0)
    out_src = jnp.where(keep, src, T)
    out = jnp.zeros((T + 1, d), x2d.dtype).at[out_src].add(
        gate[:, None] * contrib)[:T]
    out = jax.lax.psum(out, "model")
    return out.reshape(B_l, S_l, d), aux


def moe_ep(p, moe_cfg, x, *, cap_factor=1.25):
    """Expert-parallel MoE. x: (B, S, d) with batch data-sharded."""
    rules = shd.current_rules()
    mesh = rules.mesh
    R = mesh.shape["model"]
    E = moe_cfg.n_experts
    assert E % R == 0, f"experts {E} must divide model axis {R}"
    E_local = E // R
    batch = rules.act_rules.get("batch")
    if batch is None:
        batch_axes = ()
    elif isinstance(batch, tuple):
        batch_axes = batch
    else:
        batch_axes = (batch,)
    P = jax.sharding.PartitionSpec
    mesh_axes = tuple(mesh.axis_names)
    w_gate = p.get("w_gate")
    # dispatch (all-to-all) path needs the seq dim to split over 'model';
    # decode-style tiny sequences use the replicated-token path instead.
    seq_split = x.shape[1] % R == 0
    body = _local_moe if seq_split else _local_moe_replicated
    x_spec = P(batch_axes if batch_axes else None,
               "model" if seq_split else None, None)
    fn = partial(body, moe_cfg, R, E_local, cap_factor, mesh_axes)
    in_specs = (
        x_spec,                                                 # x
        P(None, None),                                          # router
        P("model", None, None),                                 # w_up
        None if w_gate is None else P("model", None, None),     # w_gate
        P("model", None, None),                                 # w_down
    )
    out_specs = (x_spec, P())
    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, p["router"]["w"], p["w_up"], w_gate, p["w_down"])
    return y, aux


def apply_moe(p, moe_cfg, x, *, force_reference=False):
    """Dispatch between EP and reference paths based on the installed mesh."""
    rules = shd.current_rules()
    use_ep = (
        not force_reference
        and rules is not None
        and rules.mesh is not None
        and "model" in rules.mesh.axis_names
        and rules.mesh.shape["model"] > 1
        and moe_cfg.n_experts % rules.mesh.shape["model"] == 0
    )
    if use_ep:
        return moe_ep(p, moe_cfg, x)
    return moe_reference(p, moe_cfg, x)
