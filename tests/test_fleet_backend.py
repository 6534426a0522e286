"""FleetBackend seam: Host vs Sharded data-plane parity.

The contracts the refactor is allowed to rely on:

- 1-shard ``ShardedFleetBackend`` refine == ``HostFleetBackend`` refine
  **bitwise** (losses, parts, per-session losses, updated head params,
  distributional memory);
- device-resident ingest/refine moves no fleet snapshot over the host
  boundary (``snapshot_h2d_bytes`` stays 0);
- multi-shard (forced host devices, subprocess) refine matches the
  unsharded estimator to fp32 tolerance — pmean'd SWD/loss aggregation,
  psum'd GMM sufficient statistics;
- ``FleetBuffer.insert_batch`` accepts ``jax.Array`` inputs (no silent
  double-conversion path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fleet import (FleetBuffer, FleetFullError, HostFleetBackend,
                              ShardedFleetBackend, T_SENTINEL_DEV,
                              make_backend)
from repro.core import gmm

DIM, N_CLASSES = 8, 4


def _head():
    def head_init(key):
        return {"w": 0.01 * jax.random.normal(key, (DIM, N_CLASSES))}

    def head_apply(p, z):
        return z @ p["w"]

    return head_init, head_apply


def _build(cls, *, capacity=4, window=12, n_components=0, seed=0):
    head_init, head_apply = _head()
    b = cls(capacity=capacity, window=window, dim=DIM, head_init=head_init,
            head_apply=head_apply, lr=0.1, seed=seed,
            n_components=n_components)
    rng = np.random.default_rng(0)
    sids = [b.admit() for _ in range(min(3, capacity))]
    for t in range(15):
        for sid in sids:
            if (t + sid) % 5 == 2:          # per-session drops -> gaps
                continue
            b.insert(sid, t, rng.normal(size=DIM).astype(np.float32),
                     label=t % N_CLASSES)
    b.evict(sids[1])
    s2 = b.admit()                          # re-admit onto the dirty row
    b.insert(s2, 0, np.ones(DIM, np.float32), label=1)
    return b


# ---------------------------------------------------------------------------
# 1-device bitwise parity (the acceptance contract)
# ---------------------------------------------------------------------------

def test_sharded_refine_bitwise_matches_host_on_one_device():
    host = _build(HostFleetBackend, n_components=6)
    shrd = _build(ShardedFleetBackend, n_components=6)
    assert shrd.shards == 1 and shrd.kind == "sharded"
    zh, mh, lh = host.snapshot()
    zs, ms, ls = shrd.snapshot()
    np.testing.assert_array_equal(zh, zs)
    np.testing.assert_array_equal(mh, ms)
    np.testing.assert_array_equal(lh, ls)
    for i in range(3):
        key = jax.random.PRNGKey(i)
        loss_h, parts_h, per_h = host.refine(key)
        loss_s, parts_s, per_s = shrd.refine(key)
        assert loss_s == loss_h, f"round {i} loss not bitwise identical"
        assert parts_s == parts_h
        np.testing.assert_array_equal(per_s, per_h)
    for a, b in zip(jax.tree.leaves(host.refiner.state.params),
                    jax.tree.leaves(shrd.refiner.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(host.memory),
                    jax.tree.leaves(shrd.memory)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_resident_refine_copies_no_snapshot():
    """The point of the sharded backend: N refine rounds move 0 snapshot
    bytes host->device, while the host backend pays (N, W, d) + masks
    per round."""
    host = _build(HostFleetBackend)
    shrd = _build(ShardedFleetBackend)
    for i in range(3):
        host.refine(jax.random.PRNGKey(i))
        shrd.refine(jax.random.PRNGKey(i))
    per_round = (host.capacity * host.window * (host.dim * 4 + 4 + 8)
                 + host.capacity)          # z f32 + mask f32 + labels i64
    assert host.snapshot_h2d_bytes == 3 * per_round
    assert shrd.snapshot_h2d_bytes == 0


# ---------------------------------------------------------------------------
# Sharded backend: FleetBuffer admission/ring semantics on device
# ---------------------------------------------------------------------------

def test_sharded_admission_eviction_and_lazy_wipe():
    b = ShardedFleetBackend(capacity=2, window=5, dim=DIM)
    sid = b.admit()
    rng = np.random.default_rng(0)
    for t in range(5):
        b.insert(sid, t, rng.normal(size=DIM).astype(np.float32), label=t % 2)
    b.evict(sid)
    assert b.n_active == 0
    # lazy: device bytes not wiped at evict time ...
    assert (np.asarray(b.z[sid]) != 0.0).any()
    # ... but the snapshot masks the evicted row completely
    z, mask, labels = b.snapshot()
    assert mask[sid].sum() == 0 and (z[sid] == 0).all() \
        and (labels[sid] == -1).all()
    with pytest.raises(KeyError):
        b.insert(sid, 6, np.ones(DIM))
    with pytest.raises(KeyError):
        b.evict(sid)
    # re-admission hands out a clean row (deferred wipe on device)
    sid2 = b.admit()
    assert sid2 == sid
    assert (np.asarray(b.z[sid2]) == 0.0).all()
    assert (np.asarray(b.t[sid2]) == T_SENTINEL_DEV).all()
    assert b.fill_fraction(sid2) == 0.0
    b.admit()
    with pytest.raises(FleetFullError):
        b.admit()


def test_sharded_rows_match_host_buffer_rows():
    """Ring semantics (wraparound, gaps, expiry, fill fraction) match the
    host FleetBuffer for identical insert histories."""
    buf = FleetBuffer(capacity=3, window=6, dim=2)
    dev = ShardedFleetBackend(capacity=3, window=6, dim=2)
    sids = [buf.admit() for _ in range(3)]
    [dev.admit() for _ in range(3)]
    rng = np.random.default_rng(1)
    for t in range(20):
        for sid in sids:
            if rng.random() < 0.3:
                continue
            z = rng.normal(size=2).astype(np.float32)
            buf.insert(sid, t + sid, z, label=t % 3)
            dev.insert(sid, t + sid, z, label=t % 3)
    zh, mh, lh = buf.snapshot()
    zd, md, ld = dev.snapshot()
    np.testing.assert_array_equal(zh, zd)
    np.testing.assert_array_equal(mh, md)
    np.testing.assert_array_equal(lh, ld)
    for sid in sids:
        assert buf.fill_fraction(sid) == pytest.approx(
            dev.fill_fraction(sid))


def test_sharded_capacity_must_divide_shards():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1), ("sessions",))
    ShardedFleetBackend(capacity=3, window=4, dim=2, mesh=mesh)  # 3 % 1 ok
    big = jax.sharding.Mesh(
        np.array([jax.devices()[0]] * 2).reshape(2), ("sessions",)) \
        if len(jax.devices()) >= 2 else None
    if big is not None:
        with pytest.raises(ValueError):
            ShardedFleetBackend(capacity=3, window=4, dim=2, mesh=big)


def test_make_backend_factory():
    assert make_backend("host", capacity=2, window=4, dim=2).kind == "host"
    assert make_backend("sharded", capacity=2, window=4,
                        dim=2).kind == "sharded"
    with pytest.raises(ValueError):
        make_backend("nope")


# ---------------------------------------------------------------------------
# Satellite: jax.Array ingest without a host round-trip / double copy
# ---------------------------------------------------------------------------

def test_fleet_buffer_insert_batch_accepts_jax_arrays():
    f_np, f_jx = (FleetBuffer(capacity=4, window=5, dim=3) for _ in range(2))
    for f in (f_np, f_jx):
        for _ in range(4):
            f.admit()
    rng = np.random.default_rng(2)
    sids, ts = np.array([0, 2, 3]), np.array([7, 1, 4])
    zs = rng.normal(size=(3, 3)).astype(np.float32)
    labs = np.array([1, -1, 0])
    f_np.insert_batch(sids, ts, zs, labs)
    f_jx.insert_batch(jnp.asarray(sids), jnp.asarray(ts), jnp.asarray(zs),
                      jnp.asarray(labs))
    np.testing.assert_array_equal(f_np.z, f_jx.z)
    np.testing.assert_array_equal(f_np.t, f_jx.t)
    np.testing.assert_array_equal(f_np.label, f_jx.label)
    np.testing.assert_array_equal(f_np.newest, f_jx.newest)


def test_sharded_insert_batch_device_arrays_move_no_payload():
    b = ShardedFleetBackend(capacity=2, window=4, dim=DIM)
    assert b.device_ingest
    b.admit()
    b.admit()
    z_dev = jnp.ones((2, DIM), jnp.float32)     # already device-resident
    b.insert_batch(np.array([0, 1]), np.array([0, 0]), z_dev)
    assert b.ingest_h2d_bytes == 0              # payload stayed on device
    b.insert_batch(np.array([0, 1]), np.array([1, 1]),
                   np.ones((2, DIM), np.float32))
    assert b.ingest_h2d_bytes == 2 * DIM * 4    # host payload counted


def test_sharded_duplicate_slot_writes_are_last_wins_like_host():
    """jnp scatter with repeated indices is undefined — the sharded
    backend must fold duplicate (sid, slot) writes to numpy's last-wins
    before dispatch, with ``newest`` still seeing the max timestamp."""
    host = FleetBuffer(capacity=2, window=4, dim=2)
    dev = ShardedFleetBackend(capacity=2, window=4, dim=2)
    for b in (host, dev):
        b.admit()
        b.admit()
    # same slot twice for sid 0 (t=1 and t=5 both hit slot 1, out of
    # order so the kept ring value and the max timestamp differ), plus a
    # normal write to sid 1
    sids = np.array([0, 1, 0])
    ts = np.array([5, 2, 1])
    zs = np.array([[5., 5.], [2., 2.], [1., 1.]], np.float32)
    labs = np.array([5, 2, 1])
    host.insert_batch(sids, ts, zs, labs)
    dev.insert_batch(sids, ts, zs, labs)
    np.testing.assert_array_equal(np.asarray(dev.z[0, 1]), host.z[0, 1])
    assert int(dev.t[0, 1]) == host.t[0, 1] == 1      # last write wins
    assert int(dev.newest[0]) == host.newest[0] == 5  # max t still seen
    zh, mh, lh = host.snapshot()
    zd, md, ld = dev.snapshot()
    np.testing.assert_array_equal(zh, zd)
    np.testing.assert_array_equal(mh, md)
    np.testing.assert_array_equal(lh, ld)


def test_backends_accept_empty_insert_batch():
    """The host buffer no-ops on an empty batch; the sharded twin must
    honor the same contract (callers batch conditionally)."""
    for cls in (HostFleetBackend, ShardedFleetBackend):
        b = cls(capacity=2, window=4, dim=DIM)
        b.admit()
        b.insert_batch(np.array([], np.int64), np.array([], np.int64),
                       np.zeros((0, DIM), np.float32))
        _, mask, _ = b.snapshot()
        assert mask.sum() == 0, cls.__name__


def test_backends_reject_memory_without_head():
    """n_components without a head is an error on BOTH backends (memory
    updates ride the refine round), not a silent divergence."""
    for cls in (HostFleetBackend, ShardedFleetBackend):
        with pytest.raises(ValueError):
            cls(capacity=2, window=4, dim=DIM, n_components=4)


# ---------------------------------------------------------------------------
# Weighted EM (the hook the fleet memory update rides on)
# ---------------------------------------------------------------------------

def test_em_update_weights_none_is_unchanged():
    key = jax.random.PRNGKey(0)
    st = gmm.init_gmm(key, 8, DIM)
    z = jax.random.normal(jax.random.PRNGKey(1), (32, DIM))
    a = gmm.em_update(st, z)
    b = gmm.em_update(st, z, weights=None)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_em_update_zero_weights_drop_frames():
    """weights=indicator == running the update on the kept subset."""
    key = jax.random.PRNGKey(0)
    st = gmm.init_gmm(key, 6, DIM)
    z = jax.random.normal(jax.random.PRNGKey(1), (24, DIM))
    keep = np.zeros(24, np.float32)
    keep[[0, 3, 7, 11, 20]] = 1.0
    a = gmm.em_update(st, z[keep > 0], reseed_frac=0.0)
    b = gmm.em_update(st, z, weights=jnp.asarray(keep), reseed_frac=0.0)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# insert_batch_placed: the sharded dispatch plane's blocked scatter
# ---------------------------------------------------------------------------

def test_insert_batch_placed_matches_plain_scatter():
    """The blocked shard-local scatter (``insert_batch_placed``) leaves
    the rings exactly as ``insert_batch``: pad rows drop, duplicate
    (sid, slot) writes keep the LAST payload, ``newest`` sees the max
    timestamp — the same fold, expressed as drop-sentinel rows."""
    rng = np.random.default_rng(0)
    a = ShardedFleetBackend(capacity=4, window=6, dim=DIM)
    b = ShardedFleetBackend(capacity=4, window=6, dim=DIM)
    for x in (a, b):
        for _ in range(3):
            x.admit()
    # three duplicates of (sid 0, slot 1): ts 7, 1 and 13 all land on
    # slot 1 — last-wins keeps ts 13's payload, newest[0] becomes 13
    sids = np.array([0, 2, 0, 1, 0])
    ts = np.array([7, 3, 1, 2, 13])
    zs = rng.normal(size=(5, DIM)).astype(np.float32)
    labels = np.array([1, 2, 3, 4, 5])
    a.insert_batch(sids, ts, jnp.asarray(zs), labels)
    blocked = np.zeros((8, DIM), np.float32)   # 3 pad rows at the tail
    rows = np.arange(5)
    blocked[rows] = zs
    b.insert_batch_placed(sids, ts,
                          jax.device_put(jnp.asarray(blocked), b._sharding),
                          labels, rows)
    for xa, xb in zip((a.z, a.t, a.label, a.newest),
                      (b.z, b.t, b.label, b.newest)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    # accounting counts the real frame payload, like insert_batch
    assert b.ingest_d2d_bytes == a.ingest_d2d_bytes == 5 * DIM * 4
    assert b.ingest_h2d_bytes == 0
    # empty batch: the host-buffer no-op contract rides along
    b.insert_batch_placed(np.array([], np.int64), np.array([], np.int64),
                          b.z[:0, 0], None, np.array([], np.int64))


def test_insert_batch_placed_validates_inputs():
    b = ShardedFleetBackend(capacity=4, window=6, dim=DIM)
    b.admit()
    z1 = jax.device_put(jnp.zeros((2, DIM), jnp.float32), b._sharding)
    with pytest.raises(TypeError):     # host payloads go via insert_batch
        b.insert_batch_placed(np.array([0]), np.array([0]),
                              np.zeros((2, DIM), np.float32), None,
                              np.array([0]))
    with pytest.raises(KeyError):      # inactive session
        b.insert_batch_placed(np.array([3]), np.array([0]), z1, None,
                              np.array([0]))
    with pytest.raises(ValueError, match="int32"):
        b.insert_batch_placed(np.array([0]), np.array([2 ** 40]), z1, None,
                              np.array([0]))


# ---------------------------------------------------------------------------
# Multi-shard: forced host devices (subprocess -> slow/full CI lane)
# ---------------------------------------------------------------------------

_MULTI_SHARD_PARITY = """
import jax, numpy as np
assert len(jax.devices()) == 4
from repro.core.fleet import HostFleetBackend, ShardedFleetBackend

DIM, NC = 8, 4
def head_init(key): return {"w": 0.01 * jax.random.normal(key, (DIM, NC))}
def head_apply(p, z): return z @ p["w"]

def build(cls):
    b = cls(capacity=8, window=12, dim=DIM, head_init=head_init,
            head_apply=head_apply, lr=0.1, seed=0, n_components=6)
    rng = np.random.default_rng(0)
    sids = [b.admit() for _ in range(7)]   # uneven active count per shard
    # drops/draws keyed by ADMISSION index, not row id: the sharded
    # backend places least-loaded (session i lands on row i*shards mod
    # ...), so the i-th admitted session must carry the same frames on
    # both backends for the pairing below to be meaningful
    for t in range(15):
        for i, sid in enumerate(sids):
            if (t + i) % 5 == 2:
                continue
            b.insert(sid, t, rng.normal(size=DIM).astype(np.float32),
                     label=t % NC)
    b.evict(sids[2])
    return b, sids

(host, sids_h), (shrd, sids_s) = \\
    build(HostFleetBackend), build(ShardedFleetBackend)
assert shrd.shards == 4
# least-loaded placement spread the 7 admissions 2/2/2/1 across shards
assert sorted(shrd.shards_of(np.array(sids_s)).tolist()) == [0,0,1,1,2,2,3]
pair = [i for i in range(7) if i != 2]      # admission i -> row sids_*[i]
rows_h = np.array([sids_h[i] for i in pair])
rows_s = np.array([sids_s[i] for i in pair])
for i in range(3):
    key = jax.random.PRNGKey(i)
    loss_h, parts_h, per_h = host.refine(key)
    loss_s, parts_s, per_s = shrd.refine(key)
    # cross-shard pmean'd loss/SWD aggregation: fp32 reassociation only
    assert abs(loss_s - loss_h) < 1e-5, (i, loss_h, loss_s)
    for k in parts_h:
        assert abs(parts_s[k] - parts_h[k]) < 1e-5, (i, k)
    # per-session losses are row-local (fleet-shared CRN draws), so the
    # i-th admitted session matches across backends whatever row the
    # placement chose for it
    np.testing.assert_allclose(per_s[rows_s], per_h[rows_h], atol=1e-5)
# pmean'd gradients -> head parity
for a, b in zip(jax.tree.leaves(host.refiner.state.params),
                jax.tree.leaves(shrd.refiner.state.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
# psum'd GMM sufficient statistics -> memory parity
for a, b in zip(jax.tree.leaves(host.memory), jax.tree.leaves(shrd.memory)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
# device-resident: no per-round snapshot copy on any shard count
assert shrd.snapshot_h2d_bytes == 0 and host.snapshot_h2d_bytes > 0
print("OK")
"""


def test_multi_shard_refine_matches_unsharded_estimator(subproc):
    out = subproc(_MULTI_SHARD_PARITY, devices=4)
    assert "OK" in out


_LEAST_LOADED_PLACEMENT = """
import jax, numpy as np
assert len(jax.devices()) == 4
from repro.core.fleet import FleetFullError, ShardedFleetBackend

b = ShardedFleetBackend(capacity=64, window=4, dim=4)
assert b.shards == 4
sids = [b.admit() for _ in range(32)]
counts = np.bincount(b.shards_of(np.array(sids)), minlength=4)
# least-loaded placement: 32 admissions land 8/8/8/8, NOT 16/16/0/0
assert counts.tolist() == [8, 8, 8, 8], counts
# drain one shard's sessions: the next admissions refill the hole first
for sid in sids:
    if b.shard_of(sid) == 2:
        b.evict(sid)
refill = [b.admit() for _ in range(8)]
assert all(b.shard_of(s) == 2 for s in refill), refill
# fill to capacity, then the typed full error
for _ in range(64 - b.n_active):
    b.admit()
try:
    b.admit()
except FleetFullError:
    print("OK")
"""


def test_least_loaded_shard_placement_on_admit(subproc):
    """ROADMAP "per-shard load balancing of admissions": a 4-shard fleet
    spreads admissions across the mesh instead of filling shard 0 first,
    and refills the emptiest shard after a drain."""
    out = subproc(_LEAST_LOADED_PLACEMENT, devices=4)
    assert "OK" in out


_SHARDED_ESTIMATOR_HOOKS = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.gmm import em_update, init_gmm
from repro.core.swd import swd_loss
from repro.launch.mesh import make_sessions_mesh

mesh = make_sessions_mesh(4)
key = jax.random.PRNGKey(0)
z = jax.random.normal(jax.random.PRNGKey(1), (64, 16))

# pmean'd SWD: the sharded estimator averages per-shard local SWDs
sharded = jax.jit(jax.shard_map(
    lambda z: swd_loss(key, z, n_dirs=16, axis_name="sessions"),
    mesh=mesh, in_specs=(P("sessions"),), out_specs=P(),
    check_vma=False))(z)
locals_ = [float(swd_loss(key, z[i * 16:(i + 1) * 16], n_dirs=16))
           for i in range(4)]
np.testing.assert_allclose(float(sharded), np.mean(locals_), rtol=1e-5)

# psum'd GMM stats: distributed EM == global EM on the gathered batch
st = init_gmm(jax.random.PRNGKey(2), 8, 16)
upd = jax.jit(jax.shard_map(
    lambda st, z: em_update(st, z, axis_name="sessions", reseed_frac=0.0),
    mesh=mesh, in_specs=(P(), P("sessions")), out_specs=P(),
    check_vma=False))(st, z)
ref = em_update(st, z, reseed_frac=0.0)
for a, b in zip(jax.tree.leaves(upd), jax.tree.leaves(ref)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
print("OK")
"""


def test_sharded_swd_and_gmm_estimator_hooks(subproc):
    """The axis_name hooks the sharded refine rides on, pinned directly:
    pmean'd SWD == mean of per-shard SWDs; psum'd EM == global EM."""
    out = subproc(_SHARDED_ESTIMATOR_HOOKS, devices=4)
    assert "OK" in out
