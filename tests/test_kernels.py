"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps.

The suite runs on the CPU backend, where the wrappers pick Pallas
interpret mode; compiling the main-path kernel for the chip is
``tests/test_tpu_compile.py``'s job.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.swd import random_directions, sphere_prior_samples
from repro.kernels import ops, ref


def _sphere(key, shape):
    z = jax.random.normal(key, shape)
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-9)


@pytest.mark.parametrize("B,C,d", [(64, 8, 32), (200, 64, 128), (33, 16, 64),
                                   (128, 32, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_posterior_sweep(B, C, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B + C + d), 4)
    z = _sphere(ks[0], (B, d)).astype(dtype)
    mu = (0.5 * jax.random.normal(ks[1], (C, d))).astype(jnp.float32)
    var = jax.random.uniform(ks[2], (C, d), minval=0.05, maxval=0.5)
    logpi = jax.nn.log_softmax(jax.random.normal(ks[3], (C,)))
    r1, e1 = ops.gmm_posterior(z, mu, var, logpi, block_b=64)
    r2, e2 = ref.gmm_posterior_ref(z.astype(jnp.float32), mu, var, logpi)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=tol)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=tol * 5)


@pytest.mark.parametrize("B,N,d", [(32, 64, 32), (64, 256, 128),
                                   (16, 100, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_infonce_vneg_sweep(B, N, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * N + d), 3)
    z = _sphere(ks[0], (B, d)).astype(dtype)
    zp = _sphere(ks[1], (B, d)).astype(dtype)
    zn = _sphere(ks[2], (B, N, d)).astype(dtype)
    l1 = ops.infonce_vneg(z, zp, zn, tau=0.1)
    l2 = ref.infonce_vneg_ref(z.astype(jnp.float32),
                              zp.astype(jnp.float32),
                              zn.astype(jnp.float32), 0.1)
    tol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=tol,
                               rtol=1e-2)


@pytest.mark.parametrize("N,d,M", [(100, 32, 8), (256, 128, 50),
                                   (512, 64, 16), (65, 16, 4)])
def test_swd_kernel_sweep(N, d, M):
    key = jax.random.PRNGKey(N + d + M)
    x = _sphere(key, (N, d))
    s1 = float(ops.swd(jax.random.PRNGKey(1), x, n_dirs=M))
    kd, kp = jax.random.split(jax.random.PRNGKey(1))
    dirs = random_directions(kd, M, d)
    prior = sphere_prior_samples(kp, N, d)
    s2 = float(ref.swd_ref(x, prior, dirs))
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("shape", [(100,), (37, 91), (8, 16, 33), (5000,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_quant_sweep(shape, dtype):
    x = (3.0 * jax.random.normal(jax.random.PRNGKey(sum(shape)), shape)
         + 1.0).astype(dtype)
    q, sc, zo = ops.int8_quantize(x)
    q2, sc2, zo2 = ref.int8_quantize_ref(x.astype(jnp.float32))
    # bf16 inputs may round-trip to an off-by-one level on exact ties
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)
                               - q2.astype(jnp.int32)))) <= 1
    np.testing.assert_allclose(float(sc), float(sc2), rtol=1e-6)
    xd = ops.int8_dequantize(q, sc, zo)
    assert float(jnp.max(jnp.abs(xd - x.astype(jnp.float32)))) <= \
        float(sc) * 0.51 + 1e-6


@pytest.mark.parametrize("B,shape", [(1, (40, 32)), (5, (16, 16)),
                                     (17, (7,)), (64, (16, 16)),
                                     (3, (100,)), (13, (10, 8, 4)),
                                     (2, (128,)), (33, (20, 24))])
def test_wire_roundtrip_bitwise_matches_vmapped_reference(B, shape):
    """The fused wire kernel IS the vmapped quantize∘dequantize pair —
    bitwise, not allclose: ``SplitEngine.run_batch_async`` swaps one for
    the other inside the serving hot path, so any divergence would break
    the per-frame vs bucketed embedding parity contract.  Odd batch
    sizes and non-128-multiple sample lengths exercise the lane padding
    (which pads each row with its own first element, leaving per-sample
    min/max untouched)."""
    from repro.quant.int8 import dequantize, quantize
    x = (3.0 * jax.random.normal(jax.random.PRNGKey(B + sum(shape)),
                                 (B,) + shape) + 1.0)
    fused = ops.wire_roundtrip(x)
    vmapped = jax.jit(jax.vmap(lambda a: dequantize(quantize(a))))(x)
    assert fused.dtype == jnp.float32 and fused.shape == x.shape
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(vmapped))


def test_wire_roundtrip_b1_matches_per_tensor_reference():
    """At B=1 the per-sample kernel equals the per-tensor quantize of
    ``SplitEngine.run`` — the parity boundary between the batched and
    per-frame serving paths."""
    from repro.quant.int8 import dequantize, quantize
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 24, 16)) * 2.0
    fused = ops.wire_roundtrip(x)
    tensor = jax.jit(lambda a: dequantize(quantize(a)))(x)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(tensor))


@pytest.mark.parametrize("B,T,d,k", [(1, 100, 128, 5), (4, 50, 32, 3),
                                     (2, 16, 8, 7)])
def test_laplacian_kernel_sweep(B, T, d, k):
    ks = jax.random.split(jax.random.PRNGKey(B * T + d), 2)
    z = jax.random.normal(ks[0], (B, T, d))
    m = (jax.random.uniform(ks[1], (B, T)) > 0.3).astype(jnp.float32)
    l1 = float(ops.laplacian_energy(z, m, k=k))
    tots = [ref.laplacian_energy_ref(z[i], m[i], k) for i in range(B)]
    l2 = sum(float(t) for t, _ in tots) / max(
        sum(float(c) for _, c in tots), 1.0)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_kernels_match_core_implementations():
    """The kernels and the core/ layers must agree (they are the same math
    at two altitudes)."""
    from repro.core import gmm as G
    from repro.core.laplacian import dirichlet_energy
    key = jax.random.PRNGKey(0)
    st_ = G.init_gmm(key, 16, 64)
    z = _sphere(jax.random.PRNGKey(1), (64, 64))
    pi, mu, var = G.params_of(st_)
    r1, e1 = ops.gmm_posterior(z, mu, var, jnp.log(pi), block_b=64)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(G.entropy(st_, z)),
                               atol=1e-4)
    z3 = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 16))
    np.testing.assert_allclose(
        float(ops.laplacian_energy(z3, k=5)),
        float(dirichlet_energy(z3, k=5)), rtol=1e-5)
