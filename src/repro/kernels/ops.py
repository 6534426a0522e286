"""Public jit'd wrappers for the Pallas kernels: shape padding, block-size
selection, and kernel/ref dispatch.  A wrapper called without
``interpret=`` compiles its kernel for the backend it is traced on:
Mosaic on a TPU, the Pallas interpreter on the CPU (whose backend cannot
compile Pallas).  Tests may pass ``interpret=`` explicitly;
``tests/test_tpu_compile.py`` compiles the main-path kernel for a
described TPU v5e.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.gmm_posterior import gmm_posterior_pallas
from repro.kernels.infonce_vneg import infonce_vneg_pallas
from repro.kernels.int8_quant import (int8_dequantize_pallas,
                                      int8_quantize_pallas,
                                      wire_roundtrip_pallas)
from repro.kernels.laplacian_energy import laplacian_energy_pallas
from repro.kernels.swd_kernel import swd_pallas


def _resolve(interpret):
    """``None`` -> interpret on the CPU backend only.  Runs at trace time,
    so each compiled entry matches the backend it was built for."""
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _pad_rows(x, mult, value=0.0):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        padding = jnp.full((pad,) + x.shape[1:], value, x.dtype)
        x = jnp.concatenate([x, padding], 0)
    return x, n


@partial(jax.jit, static_argnames=("interpret", "block_b"))
def gmm_posterior(z, mu, var, logpi, *, block_b=128, interpret=None):
    """-> (responsibilities (B, C), entropy (B,))."""
    interpret = _resolve(interpret)
    zp, n = _pad_rows(z, block_b)
    resp, ent = gmm_posterior_pallas(zp, mu, var, logpi, block_b=block_b,
                                     interpret=interpret)
    return resp[:n], ent[:n]


@partial(jax.jit, static_argnames=("tau", "interpret", "block_b", "block_n"))
def infonce_vneg(z, z_pos, z_neg, *, tau=0.1, block_b=64, block_n=128,
                 interpret=None):
    """Per-sample streaming InfoNCE (Eq. 10). Inputs must be l2-normalized."""
    interpret = _resolve(interpret)
    B, d = z.shape
    N = z_neg.shape[1]
    bb = min(block_b, B)
    while B % bb:
        bb -= 1
    bn = min(block_n, N)
    while N % bn:
        bn -= 1
    return infonce_vneg_pallas(z, z_pos, z_neg, tau=tau, block_b=bb,
                               block_n=bn, interpret=interpret)


@partial(jax.jit, static_argnames=("n_dirs", "interpret"))
def swd(key, x, *, n_dirs=50, interpret=None):
    """Sliced-W2² to the uniform sphere prior, fully fused (Eq. 3)."""
    interpret = _resolve(interpret)
    from repro.core.swd import random_directions, sphere_prior_samples
    N, d = x.shape
    kd, kp = jax.random.split(key)
    dirs = random_directions(kd, n_dirs, d)
    prior = sphere_prior_samples(kp, N, d)
    n_pow2 = 1 << max((N - 1).bit_length(), 3)
    xp, _ = _pad_rows(x.astype(jnp.float32), n_pow2)
    pq = jnp.sort(prior @ dirs.T, axis=0)                  # (N, M)
    pq = jnp.concatenate(
        [pq, jnp.zeros((n_pow2 - N, n_dirs), jnp.float32)], 0)
    return swd_pallas(xp, pq, dirs, valid_n=N, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def int8_quantize(x, *, interpret=None):
    return int8_quantize_pallas(x, interpret=_resolve(interpret))


@partial(jax.jit, static_argnames=("interpret", "dtype"))
def int8_dequantize(q, scale, zero, *, dtype=jnp.float32, interpret=None):
    return int8_dequantize_pallas(q, scale, zero, dtype=dtype,
                                  interpret=_resolve(interpret))


@partial(jax.jit, static_argnames=("interpret", "block_b"))
def wire_roundtrip(x, *, block_b=8, interpret=None):
    """Fused per-sample INT8 quantize∘dequantize over the leading batch
    dim — the split-link wire stage of ``SplitEngine.run_batch_async``.
    Bitwise-equal to ``jax.vmap(lambda a: dequantize(quantize(a)))``
    (pinned in tests/test_kernels.py), so the per-frame vs bucketed
    bit-parity contract survives the fusion.  The engine traces it
    inside one executable with the edge and server stages: on the TPU
    it lowers to a Mosaic custom call, whose own arithmetic no XLA
    fusion reaches.  The jnp reference is not so sealed and runs
    un-fused (``run_batch``)."""
    return wire_roundtrip_pallas(x, block_b=block_b,
                                 interpret=_resolve(interpret))


@partial(jax.jit, static_argnames=("k", "interpret"))
def laplacian_energy(z, mask=None, *, k=5, interpret=None):
    if z.ndim == 2:
        z = z[None]
    if mask is None:
        mask = jnp.ones(z.shape[:2], jnp.float32)
    elif mask.ndim == 1:
        mask = mask[None]
    return laplacian_energy_pallas(z, mask, k=k, interpret=_resolve(interpret))
