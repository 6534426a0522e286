"""Distributional Memory (paper §4.1): streaming EM, uncertainty, sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmm as G


def _sphere(key, n, d):
    z = jax.random.normal(key, (n, d))
    return z / jnp.linalg.norm(z, axis=-1, keepdims=True)


def test_responsibilities_normalized():
    key = jax.random.PRNGKey(0)
    st_ = G.init_gmm(key, 8, 16)
    z = _sphere(jax.random.PRNGKey(1), 32, 16)
    r = G.responsibilities(st_, z)
    np.testing.assert_allclose(np.asarray(r.sum(-1)), 1.0, rtol=1e-5)
    assert bool((r >= 0).all())


# seeded sweep over (components, dim, batch) — corners + odd interior sizes
@pytest.mark.parametrize("C,d,B", [
    (2, 2, 1), (2, 64, 48), (32, 2, 1), (32, 64, 48),
    (3, 5, 2), (8, 16, 32), (16, 8, 3), (7, 33, 17),
    (2, 3, 48), (32, 17, 7), (5, 64, 1), (13, 13, 13),
])
def test_entropy_bounds(C, d, B):
    key = jax.random.PRNGKey(C * 1000 + d)
    st_ = G.init_gmm(key, C, d)
    z = _sphere(jax.random.PRNGKey(B), B, d)
    u = G.entropy(st_, z)
    assert bool((u >= -1e-5).all())
    assert bool((u <= np.log(C) + 1e-4).all())
    un = G.normalized_entropy(st_, z)
    assert bool((un <= 1.0 + 1e-5).all())


def test_em_convergence_recovers_clusters():
    """Streaming EM on a 4-cluster synthetic mixture: post-fit likelihood
    must beat the init and responsibilities become confident."""
    key = jax.random.PRNGKey(0)
    d, C = 16, 4
    centers = _sphere(jax.random.PRNGKey(5), C, d)
    st_ = G.init_gmm(key, C, d, var0=0.5)

    def batch(k):
        ks = jax.random.split(k, 2)
        idx = jax.random.randint(ks[0], (64,), 0, C)
        z = centers[idx] + 0.05 * jax.random.normal(ks[1], (64, d))
        return z / jnp.linalg.norm(z, axis=-1, keepdims=True)

    z0 = batch(jax.random.PRNGKey(99))
    ll_before = float(jax.nn.logsumexp(G.log_joint(st_, z0), -1).mean())
    for i in range(150):
        st_ = G.em_update(st_, batch(jax.random.PRNGKey(i)), decay=0.05)
    ll_after = float(jax.nn.logsumexp(G.log_joint(st_, z0), -1).mean())
    assert ll_after > ll_before + 1.0
    u = G.normalized_entropy(st_, z0)
    assert float(u.mean()) < 0.5  # confident assignments


def test_boundary_sampling_excludes_anchor_component():
    key = jax.random.PRNGKey(0)
    st_ = G.init_gmm(key, 8, 16)
    z = _sphere(jax.random.PRNGKey(1), 16, 16)
    c_star = G.assign(st_, z)
    logits = G.boundary_logits(st_, c_star)
    own = jnp.take_along_axis(logits, c_star[:, None], 1)
    assert bool(jnp.all(own == -jnp.inf))


def test_virtual_negatives_on_sphere_and_shape():
    key = jax.random.PRNGKey(0)
    st_ = G.init_gmm(key, 8, 16)
    z = _sphere(jax.random.PRNGKey(1), 4, 16)
    neg = G.sample_virtual_negatives(jax.random.PRNGKey(2), st_, z, 32)
    assert neg.shape == (4, 32, 16)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(neg), axis=-1),
                               1.0, rtol=1e-4)


def test_memory_footprint_under_35kb():
    """Paper Eq. 8: C=64, d=128 fp16 distributional memory ≈ 33 KB."""
    st_ = G.init_gmm(jax.random.PRNGKey(0), 64, 128)
    assert G.size_bytes(st_, dtype_bytes=2) <= 35 * 1024


def test_distributed_em_matches_single(subproc):
    """psum'd sufficient stats == concatenated-batch update."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import gmm as G
mesh = make_mesh((4,), ('data',))
key = jax.random.PRNGKey(0)
st = G.init_gmm(key, 4, 8)
z = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
z = z / jnp.linalg.norm(z, axis=-1, keepdims=True)
ref = G.em_update(st, z, decay=0.1)
def local(st, z):
    return G.em_update(st, z, decay=0.1, axis_name='data')
out = jax.jit(jax.shard_map(local, mesh=mesh,
    in_specs=(P(), P('data')), out_specs=P(), check_vma=False))(st, z)
for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6)
print('distributed EM OK')
""", devices=4)
