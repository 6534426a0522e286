"""Split execution engine.

1. ``SplitEngine`` — the paper's mechanism on the paper's model: run blocks
   [0, k) as the *edge stage*, INT8-quantize the boundary activation (the
   wire payload), run blocks [k, L) + head as the *server stage*.  One
   compiled executable per k, switched atomically at step boundaries
   (§4.2.2 "Atomic Transitions": recompiling/ switching between steps —
   never mid-block).

2. ``split_pipeline_podwise`` — the TPU-native adaptation: a 2-stage SPMD
   pipeline over the 'pod' mesh axis (shard_map + collective_permute),
   with the inter-stage activation optionally INT8 on the wire.  Stage
   boundary k = L/2 (SPMD requires equal stages; DESIGN.md §2 records this
   constraint).  This is the multi-pod dry-run's "paper technique" cell.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.models import audio_encoder as enc
from repro.quant.int8 import dequantize, fake_quant, quantize


class SplitEngine:
    """Compiled-per-k split executor for the audio encoder.

    Per-k executables are built lazily on first use: a session that only
    ever runs one k compiles 2 callables, not ``2·(L+1)`` — this is what
    keeps ``StreamSplitGateway`` startup O(1) in L.  Atomic-transition
    semantics are unchanged: each k still gets its own executable, and
    switching k selects a whole different compiled program at a step
    boundary, never mid-block.
    """

    def __init__(self, cfg: enc.AudioEncCfg, *, quantize_wire=True):
        self.cfg = cfg
        self.quantize_wire = quantize_wire
        self._edge = {}
        self._server = {}
        # The INT8 wire round-trip runs as its OWN jitted executable,
        # never fused into the edge/server stages: fusing it changes the
        # rounding of the affine chain, and the per-frame vs k-bucketed
        # bit-parity contract (tests/test_gateway.py) depends on both
        # paths quantizing with the same compiled program.  ``run``
        # quantizes per tensor (one scale/zero for its whole batch);
        # ``run_batch`` per sample — identical at B=1, which is exactly
        # the parity boundary.
        self._qdq_tensor = jax.jit(lambda a: dequantize(quantize(a)))
        self._qdq_sample = jax.jit(jax.vmap(lambda a: dequantize(quantize(a))))

    def _edge_exec(self, k):
        if k not in self._edge:
            self._edge[k] = jax.jit(partial(self._edge_fn, k))
        return self._edge[k]

    def _server_exec(self, k):
        if k not in self._server:
            self._server[k] = jax.jit(partial(self._server_fn, k))
        return self._server[k]

    def _edge_fn(self, k, params, mel):
        if k == 0:
            # k=0 is raw-input offload: the wire carries the model input and
            # the server runs the stem — matches boundary_bytes(cfg)[0].
            return mel
        x = enc.apply_stem(self.cfg, params, mel)
        x = enc.apply_blocks(self.cfg, params, x, 0, k)
        if k == self.cfg.n_blocks:
            return enc.apply_head(self.cfg, params, x)
        return x

    def _server_fn(self, k, params, x):
        if k == 0:
            x = enc.apply_stem(self.cfg, params, x)
        x = enc.apply_blocks(self.cfg, params, x, k, self.cfg.n_blocks)
        return enc.apply_head(self.cfg, params, x)

    def run(self, params, mel, k):
        """-> (embedding z, wire_bytes)."""
        L = self.cfg.n_blocks
        k = int(k)
        if k >= L:
            return self._edge_exec(L)(params, mel), 0
        act = self._edge_exec(k)(params, mel)
        if self.quantize_wire:
            wire_bytes = act.size + 8     # int8 payload + scale/zero header
            act = self._qdq_tensor(act)   # "received" on the server
        else:
            wire_bytes = act.size * 4
        z = self._server_exec(k)(params, act)
        return z, wire_bytes

    def run_batch(self, params, mel, k):
        """Run B frames that share one split index as ONE dispatch per stage.

        -> (z (B, d), wire_bytes per frame).  The serving hot path of
        ``api/gateway.py``: every session bucketed at the same k rides a
        single padded edge dispatch, a per-sample (vmapped) INT8 wire
        round-trip in its own executable, and a single server dispatch.
        Keeping the wire stage un-fused is what keeps the batch
        bit-identical to B separate ``run`` calls (see ``__init__``; the
        gateway parity test pins this).  Per-frame wire bytes equal
        ``run``'s on a single-frame batch: payload + 8-byte scale/zero
        header.
        """
        L = self.cfg.n_blocks
        k = int(k)
        if k >= L:
            return self._edge_exec(L)(params, mel), 0
        act = self._edge_exec(k)(params, mel)
        per_frame = act.size // act.shape[0]
        if self.quantize_wire:
            act = self._qdq_sample(act)
            wire_bytes = per_frame + 8    # int8 payload + scale/zero header
        else:
            wire_bytes = per_frame * 4
        z = self._server_exec(k)(params, act)
        return z, wire_bytes

    def run_batch_async(self, params, mel, k):
        """``run_batch`` without ever materializing on the host: accepts a
        device-resident mel batch, returns the **unmaterialized** device
        embedding — no block, no device→host copy.  The caller owns the
        tick's single sync point (``StreamSplitGateway.tick``), so B
        buckets overlap on the device instead of paying one round-trip
        each.

        The wire stage runs the fused Pallas ``wire_roundtrip`` kernel
        (``kernels/int8_quant.py``) — still its OWN executable, never
        fused into the edge/server stages, and pinned bitwise against the
        vmapped ``quantize∘dequantize`` reference that ``run_batch``
        executes — so embeddings stay bit-identical to both the PR-3 sync
        path and B separate ``run`` calls.
        """
        L = self.cfg.n_blocks
        k = int(k)
        if k >= L:
            return self._edge_exec(L)(params, mel), 0
        # k=0 offloads the raw input: _edge_fn(0) is the identity, so the
        # dispatch skips its executable entirely (bitwise no-op, one less
        # host->device program launch on the hot path)
        act = mel if k == 0 else self._edge_exec(k)(params, mel)
        per_frame = act.size // act.shape[0]
        if self.quantize_wire:
            act = kernel_ops.wire_roundtrip(act)
            wire_bytes = per_frame + 8    # int8 payload + scale/zero header
        else:
            wire_bytes = per_frame * 4
        z = self._server_exec(k)(params, act)
        return z, wire_bytes

    def full(self, params, mel):
        return self._edge_exec(self.cfg.n_blocks)(params, mel)


# ---------------------------------------------------------------------------
# Pod-axis 2-stage SPMD pipeline (the TPU adaptation of the split link)
# ---------------------------------------------------------------------------

def split_pipeline_podwise(mesh, stage_fn, params_stacked, x_microbatches,
                           *, quantize_wire=True, batch_axes=("data",)):
    """2-stage pipeline across the 'pod' axis.

    stage_fn(stage_params, h) -> h' applies half the layer stack; params
    are stacked (2, ...) and sharded so pod 0 holds stage 0 and pod 1
    stage 1.  Microbatches stream through: pod 0 computes stage 0 on
    microbatch t while pod 1 computes stage 1 on microbatch t-1; the
    boundary activation crosses the pod link via collective_permute,
    INT8-quantized (fake-quant in-graph; wire bytes = size/4).

    x_microbatches: (M, mb, ...) -> returns (M, mb, ...) stage-1 outputs.
    """
    P = jax.sharding.PartitionSpec
    M = x_microbatches.shape[0]
    n_pods = mesh.shape["pod"]
    assert n_pods == 2, "2-stage pipeline"

    def local_fn(xs, stage_params):
        # xs: (M, mb_local, ...) identical copy on both pods (batch sharded
        # over data axes only); stage_params: this pod's stage (leading dim 1)
        sp = jax.tree.map(lambda t: t[0], stage_params)
        pod = jax.lax.axis_index("pod")

        def step(carry, x_t):
            h_prev = carry
            # stage input: pod0 <- fresh microbatch, pod1 <- permuted act
            h_in = jnp.where(pod == 0, x_t, h_prev)
            h_out = stage_fn(sp, h_in)
            if quantize_wire:
                h_out = fake_quant(h_out)
            h_next = jax.lax.ppermute(h_out, "pod", [(0, 1)])
            # pod1's h_out is the finished microbatch
            return h_next, h_out

        pad = jnp.zeros_like(xs[0])
        xs_pad = jnp.concatenate([xs, pad[None]], 0)   # one drain step
        _, outs = jax.lax.scan(step, jnp.zeros_like(xs[0]), xs_pad)
        # finished outputs live on pod 1 at steps 1..M; broadcast to pod 0
        finished = outs[1:]
        finished = jnp.where(pod == 1, finished, jnp.zeros_like(finished))
        finished = jax.lax.psum(finished, "pod")
        return finished

    ndim = x_microbatches.ndim
    x_spec = P(None, batch_axes, *([None] * (ndim - 2)))
    in_specs = (x_spec, P("pod"))
    out_specs = x_spec
    return jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
        x_microbatches, params_stacked)
