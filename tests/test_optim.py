"""Optimizers: formula checks + convergence + compression parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adafactor_init, adafactor_update, adamw_init,
                         adamw_update, sgd_init, sgd_update)
from repro.optim.compression import ErrorFeedback, compress_decompress
from repro.optim.schedules import warmup_cosine


def test_adamw_first_step_formula():
    p = {"w": jnp.array([1.0, -2.0])}
    g = {"w": jnp.array([0.5, 0.25])}
    st = adamw_init(p)
    p2, st2 = adamw_update(p, g, st, lr=0.1, b1=0.9, b2=0.999, eps=1e-8,
                           grad_clip=0.0)
    # after bias correction the first step is -lr * g/(|g|+eps) = -lr*sign
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.asarray(p["w"]) - 0.1 * np.sign([0.5, 0.25]),
                               rtol=1e-4)


def _quadratic_losses(update_fn, init_fn, steps=200, lr=0.05, **kw):
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (8, 8))
    A = A @ A.T / 8 + jnp.eye(8)
    b = jax.random.normal(jax.random.PRNGKey(1), (8,))
    params = {"x": jnp.zeros((8,)), "W": jnp.zeros((8, 8))}

    def loss(p):
        r = A @ p["x"] - b
        return 0.5 * r @ r + 0.5 * jnp.sum((p["W"] - A) ** 2)

    st = init_fn(params)
    hist = []
    for _ in range(steps):
        g = jax.grad(loss)(params)
        params, st = update_fn(params, g, st, lr=lr, **kw)
        hist.append(float(loss(params)))
    return hist


@pytest.mark.parametrize("opt", ["adamw", "adafactor", "sgd"])
def test_optimizers_converge_on_quadratic(opt):
    fns = {"adamw": (adamw_update, adamw_init),
           "adafactor": (adafactor_update, adafactor_init),
           "sgd": (sgd_update, sgd_init)}
    upd, init = fns[opt]
    hist = _quadratic_losses(upd, init, lr=0.05 if opt != "sgd" else 0.01)
    assert hist[-1] < hist[0] * 0.05, f"{opt}: {hist[0]} -> {hist[-1]}"


def test_adafactor_memory_is_factored():
    p = {"w": jnp.zeros((256, 512)), "b": jnp.zeros((512,))}
    st = adafactor_init(p)
    n_state = sum(int(x.size) for x in jax.tree.leaves(st["stats"]))
    n_param = 256 * 512 + 512
    assert n_state < n_param * 0.02  # rows+cols << full matrix


def test_schedule_warmup_then_decay():
    lrs = [float(warmup_cosine(s, peak=1.0, warmup=10, total=100))
           for s in range(100)]
    assert lrs[0] < lrs[5] < lrs[10 - 1]
    assert lrs[20] > lrs[60] > lrs[99]


def test_error_feedback_preserves_signal():
    """EF accumulates what compression drops: sum of applied updates over
    T steps ≈ sum of raw gradients (bounded residual)."""
    key = jax.random.PRNGKey(0)
    g_total = jnp.zeros((64,))
    applied_total = jnp.zeros((64,))
    ef = {"g": jnp.zeros((64,))}
    for t in range(50):
        g = {"g": jax.random.normal(jax.random.PRNGKey(t), (64,)) * 0.1}
        out, ef = ErrorFeedback.apply(g, ef)
        g_total += g["g"]
        applied_total += out["g"]
    resid = float(jnp.max(jnp.abs(g_total - applied_total)))
    # residual is at most one step's quantization error, not O(T)
    assert resid < 0.05


def test_compressed_dp_matches_uncompressed(subproc):
    """int8+EF data-parallel training reaches the same optimum as exact
    psum on a quadratic (4-way DP)."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.distributed.grad_sync import make_compressed_dp_step, ef_init
from repro.optim import sgd_init, sgd_update

mesh = make_test_mesh((4,), ('data',))
A = jnp.eye(8)
def loss_fn(params, batch):
    r = batch['x'] @ params['w'] - batch['y']
    return jnp.mean(r * r)
key = jax.random.PRNGKey(0)
w_true = jax.random.normal(key, (8, 4))
X = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
Y = X @ w_true
params = {'w': jnp.zeros((8, 4))}
outs = {}
for compress in (False, True):
    p = {'w': jnp.zeros((8, 4))}
    st = sgd_init(p)
    ef = ef_init(p)
    step = make_compressed_dp_step(mesh, loss_fn, sgd_update, axis='data',
                                   lr=0.1, compress=compress)
    for i in range(200):
        p, st, ef = step(p, st, ef, {'x': X, 'y': Y})
    outs[compress] = float(loss_fn(p, {'x': X, 'y': Y}))
print('exact', outs[False], 'compressed', outs[True])
assert outs[False] < 1e-4
assert outs[True] < 1e-3
""", devices=4)


def test_int8_psum_wire_accuracy(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.optim.compression import int8_psum
mesh = make_test_mesh((4,), ('data',))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
def f(x):
    return int8_psum(x, 'data'), jax.lax.psum(x, 'data')
got, want = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P('data'),
    out_specs=(P(), P()), check_vma=False))(x)
rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
print('rel err', rel)
assert rel < 0.05
""", devices=4)
