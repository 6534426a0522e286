"""Asymmetric INT8 quantization Pallas kernels — the split-link wire
format (paper §5 "Quantization Implementation", <0.5 ms class).

Two-pass: (1) blockwise min/max reduction, (2) fused affine quantize with
the agreed per-tensor scale/zero.  Both passes stream 1-D tiles through
VMEM; pass 2 writes int8 — a 4x HBM-write saving vs fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _minmax_kernel(x_ref, lo_ref, hi_ref):
    x = x_ref[...].astype(jnp.float32)
    lo_ref[...] = jnp.min(x, keepdims=True).reshape(lo_ref.shape)
    hi_ref[...] = jnp.max(x, keepdims=True).reshape(hi_ref.shape)


def _quant_kernel(x_ref, sz_ref, q_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = sz_ref[0]
    zero = sz_ref[1]
    q = jnp.clip(jnp.round(x / scale + zero), -128, 127)
    q_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(q_ref, sz_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = ((q - sz_ref[1]) * sz_ref[0]).astype(x_ref.dtype)


def int8_quantize_pallas(x, *, block=4096, interpret=True):
    """-> (q int8 flat-shaped-like-x, scale (), zero ())."""
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.full((pad,), flat[0], flat.dtype)])
    g = flat.shape[0] // block
    lo, hi = pl.pallas_call(
        _minmax_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((1,), lambda i: (i,)),
                   pl.BlockSpec((1,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((g,), jnp.float32),
                   jax.ShapeDtypeStruct((g,), jnp.float32)],
        interpret=interpret,
    )(flat)
    lo = jnp.min(lo)
    hi = jnp.max(hi)
    scale = jnp.maximum((hi - lo) / 255.0, 1e-12)
    zero = -128.0 - lo / scale
    sz = jnp.stack([scale, zero])
    q = pl.pallas_call(
        _quant_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((2,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((g * block,), jnp.int8),
        interpret=interpret,
    )(flat, sz)
    q = q[:n].reshape(shape)
    return q, scale, zero


def _wire_roundtrip_kernel(x_ref, out_ref):
    """A (block_b, n) tile of samples per grid step: row-wise min/max
    reduction, affine quantize to the int8 grid and requantize back to
    fp32 — one VMEM pass, no int8 tensor ever written to HBM.  The
    arithmetic is kept op-for-op identical to per-sample
    ``quant.int8.dequantize(quantize(x))`` (row min/max are exactly
    associative, the affine chain is elementwise), which is what makes
    the bitwise pin against the vmapped reference possible."""
    x = x_ref[...].astype(jnp.float32)
    lo = jnp.min(x, axis=1, keepdims=True)
    hi = jnp.max(x, axis=1, keepdims=True)
    scale = jnp.maximum((hi - lo) / 255.0, 1e-12)
    zero = -128.0 - lo / scale
    q = jnp.clip(jnp.round(x / scale + zero), -128, 127).astype(jnp.int8)
    out_ref[...] = (q.astype(jnp.float32) - zero) * scale


def wire_roundtrip_pallas(x, *, block_b=8, interpret=True):
    """Fused per-sample INT8 wire simulation: ``vmap(dequantize∘quantize)``
    over the leading (batch) dim as ONE kernel.

    The two-executable path (``int8_quantize_pallas`` +
    ``int8_dequantize_pallas``) writes the int8 payload to HBM and reads
    it back; serving only needs the *received* activation, so the fused
    kernel keeps each sample's tile in VMEM through reduce → quantize →
    requantize and writes fp32 once.  ``block_b`` rows ride one grid step
    — (8, 128·m) tiles, the fp32 minimum on TPU.  -> same shape as ``x``,
    float32, bitwise-equal to the vmapped reference in interpret mode
    (tests/test_kernels.py); tests/test_tpu_compile.py compiles it for a
    TPU v5e, and ``chip_smoke.py`` checks it against that reference on
    the chip.
    """
    B = x.shape[0]
    shape = x.shape
    flat = x.reshape(B, -1).astype(jnp.float32)
    n = flat.shape[1]
    pad_n = (-n) % 128               # lane-width alignment for the TPU path
    if pad_n:
        # pad each row with its OWN first element: per-sample min/max —
        # and therefore every quantization constant — is unchanged
        flat = jnp.concatenate(
            [flat, jnp.broadcast_to(flat[:, :1], (B, pad_n))], axis=1)
    bb = min(block_b, B)
    pad_b = (-B) % bb                # pad rows quantize too, sliced off
    if pad_b:
        flat = jnp.concatenate(
            [flat, jnp.broadcast_to(flat[:1], (pad_b,) + flat.shape[1:])])
    out = pl.pallas_call(
        _wire_roundtrip_kernel,
        grid=(flat.shape[0] // bb,),
        in_specs=[pl.BlockSpec((bb, flat.shape[1]), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, flat.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, jnp.float32),
        interpret=interpret,
    )(flat)
    return out[:B, :n].reshape(shape)


def int8_dequantize_pallas(q, scale, zero, *, block=4096, dtype=jnp.float32,
                           interpret=True):
    shape = q.shape
    flat = q.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    g = flat.shape[0] // block
    sz = jnp.stack([scale, zero])
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((2,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((g * block,), dtype),
        interpret=interpret,
    )(flat, sz)
    return x[:n].reshape(shape)
