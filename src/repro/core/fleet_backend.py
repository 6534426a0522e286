"""The fleet data plane behind one ``FleetBackend`` seam.

The gateway (``api/gateway.py``) serves millions of streams through a
single abstraction that owns session rows, ingest, and refinement:

- ``HostFleetBackend`` — the original single-host path: ``FleetBuffer``
  rings in host numpy, one ``(N, W, d)`` snapshot copied to the device
  per refinement round, ``FleetRefiner`` in one jit.
- ``ShardedFleetBackend`` — the scaling path (parallel split learning:
  EPSL arXiv:2403.15815, AdaSplit arXiv:2112.01637): session rings live
  **on device** as ``jax.Array``s sharded over a ``sessions`` mesh axis,
  inserts are donated in-place ``.at[]`` scatters (no per-round snapshot
  copy — the refine step reads the rings where they already are), and
  ``refine`` runs under ``shard_map``: per-shard hybrid losses with the
  cross-shard active-session normalizer ``psum``'d (the estimator family
  of ``swd_loss(axis_name=...)``), gradients ``pmean``'d via
  ``distributed.grad_sync``, and the optional distributional memory
  updated with ``gmm.em_update(axis_name=...)``'s psum'd sufficient
  statistics.  One refine step trains on the whole fleet across the mesh.

Contracts (pinned in ``tests/test_fleet_backend.py``):
- a 1-shard ``ShardedFleetBackend`` refine is **bit-identical** to
  ``HostFleetBackend`` (losses, parts, per-session losses, updated head);
- a multi-shard refine matches the unsharded estimator to fp32 tolerance
  (the only cross-shard reassociations are the pmean/psum reductions);
- both report host<->device traffic (``snapshot_h2d_bytes`` /
  ``ingest_h2d_bytes``) so ``benchmarks/fleet_serve.py`` can show the
  snapshot copy is gone.

The gateway's overlapped tick (docs/PERF.md) stages every frame as one
device array and hands the submission-ordered dispatch embeddings to
``insert_batch`` as a ``jax.Array``: on the sharded backend the payload
flows dispatch → rings entirely on device (``ingest_h2d_bytes`` stays 0;
the zero-copy volume is measured in ``ingest_d2d_bytes``).

Both backends are **thread-safe by contract**: every state transition
(admit/evict/insert/refine/snapshot) holds one re-entrant lock, because
the streaming runtime (``serving/server.py``) ingests from a background
serving thread while clients open/close sessions from their own.  The
sharded backend additionally places admissions **least-loaded** across
the session mesh (ROADMAP: per-shard load balancing) — see
``ShardedFleetBackend.admit``.
"""
from __future__ import annotations

import abc
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import gmm
from repro.core.fleet_buffer import (T_SENTINEL, FleetBuffer, FleetFullError,
                                     as_host, pad_pow2)
from repro.core.fleet_refiner import FleetRefiner, make_fleet_loss
from repro.core.hybrid import HybridCfg
from repro.distributed.grad_sync import pmean_grads
from repro.distributed.sharding import SESSIONS_AXIS, sessions_sharding

# Device rings are int32 (jax default int width without x64): the sentinel
# is the int32 minimum, still far below any reachable window index -(W+1).
T_SENTINEL_DEV = int(np.iinfo(np.int32).min)


class FleetBackend(abc.ABC):
    """Everything the gateway needs from the fleet data plane.

    ``capacity``/``window``/``dim`` describe the (N, W, d) session rings;
    ``shards`` is 1 on the host backend and the ``sessions`` mesh-axis
    size on the sharded one.  ``snapshot_h2d_bytes`` accumulates fleet
    snapshot bytes copied host->device for refinement (the cost the
    device-resident backend eliminates); ``ingest_h2d_bytes`` accumulates
    frame payload bytes moved host->device at ingest, and
    ``ingest_d2d_bytes`` the payload that arrived as ``jax.Array``s and
    never crossed the host boundary (the gateway's staged dispatch path).
    """

    capacity: int
    window: int
    dim: int
    shards: int = 1
    kind: str = "abstract"
    # True when insert_batch can consume jax.Arrays without a host
    # round-trip — the gateway hands over device embeddings directly
    device_ingest: bool = False
    snapshot_h2d_bytes: int = 0
    ingest_h2d_bytes: int = 0
    ingest_d2d_bytes: int = 0

    def __init__(self):
        # Ingest is thread-safe by contract: the streaming runtime
        # (``serving/server.py``) drives admit/insert/refine from its
        # serving thread while clients open/close sessions from their
        # own — every state transition in a concrete backend holds this
        # re-entrant lock.
        self._lock = threading.RLock()

    # -- session lifecycle ---------------------------------------------------
    @property
    @abc.abstractmethod
    def n_active(self) -> int: ...

    @abc.abstractmethod
    def admit(self) -> int: ...

    @abc.abstractmethod
    def evict(self, sid) -> None: ...

    # -- ingest --------------------------------------------------------------
    @abc.abstractmethod
    def insert(self, sid, t, z, label=-1) -> None: ...

    @abc.abstractmethod
    def insert_batch(self, sids, ts, zs, labels=None) -> None: ...

    @abc.abstractmethod
    def fill_fraction(self, sid) -> float: ...

    # -- row migration (cluster federation; docs/FEDERATION.md) --------------
    @abc.abstractmethod
    def export_row(self, sid):
        """Copy one session's ring row out of the fleet:
        ``(z (W, d) f32, t (W,) i64, label (W,) i64, newest int)`` in the
        HOST representation (``fleet_buffer.T_SENTINEL`` marks empty
        slots) regardless of backend — so a row exported from any
        backend implants into any other."""

    @abc.abstractmethod
    def import_row(self, sid, z, t, label, newest) -> None:
        """Implant an exported row into an admitted session slot (the
        inverse of ``export_row``; host-representation inputs)."""

    # -- refinement ----------------------------------------------------------
    @property
    def can_refine(self) -> bool:
        return getattr(self, "refiner", None) is not None

    @abc.abstractmethod
    def refine(self, key):
        """One fleet-wide hybrid-loss step.
        -> (mean active loss, mean active parts, per-session losses (N,))."""

    # -- observability -------------------------------------------------------
    @abc.abstractmethod
    def snapshot(self):
        """Host-side (z (N, W, d), mask (N, W), labels (N, W))."""

    def shards_of(self, sids) -> np.ndarray:
        """Which session shard each fleet row lives on (contiguous
        blocks) — THE placement contract; override in lockstep with the
        mesh layout."""
        return np.asarray(sids, np.int64) * self.shards // self.capacity

    def shard_of(self, sid) -> int:
        return int(self.shards_of(np.array([sid]))[0])


class HostFleetBackend(FleetBackend):
    """The original single-host data plane behind the backend seam:
    numpy ``FleetBuffer`` rings + ``FleetRefiner``; every refine round
    copies one full fleet snapshot to the device (counted in
    ``snapshot_h2d_bytes``)."""

    kind = "host"

    def __init__(self, *, capacity=32, window=100, dim=128, head_init=None,
                 head_apply=None, cfg: HybridCfg = HybridCfg(), lr=1e-2,
                 seed=0, n_components=0, memory_decay=0.05):
        super().__init__()
        if n_components and head_init is None:
            raise ValueError("fleet memory (n_components) updates ride the "
                             "refine round: pass head_init/head_apply too")
        self.capacity, self.window, self.dim = capacity, window, dim
        self.shards = 1
        self.buffer = FleetBuffer(capacity=capacity, window=window, dim=dim)
        self.refiner = None
        if head_init is not None:
            self.refiner = FleetRefiner(head_init, head_apply, cfg=cfg,
                                        lr=lr, seed=seed)
        self.memory = None
        if n_components:
            self.memory = gmm.init_gmm(jax.random.PRNGKey(seed + 1),
                                       n_components, dim)
            # reseed stays off for fleet memory: reseeding picks rows of
            # the local batch, which would de-replicate the state across
            # shards on the sharded twin — keep both backends identical
            self._em = jax.jit(partial(gmm.em_update, decay=memory_decay,
                                       reseed_frac=0.0))
        self.snapshot_h2d_bytes = 0
        self.ingest_h2d_bytes = 0

    # -- delegation to the host buffer --------------------------------------
    @property
    def n_active(self):
        return self.buffer.n_active

    @property
    def active(self):
        return self.buffer.active

    def admit(self):
        with self._lock:
            return self.buffer.admit()

    def evict(self, sid):
        with self._lock:
            self.buffer.evict(sid)

    def insert(self, sid, t, z, label=-1):
        with self._lock:
            self.buffer.insert(sid, t, z, label=label)

    def insert_batch(self, sids, ts, zs, labels=None):
        with self._lock:
            self.buffer.insert_batch(sids, ts, zs, labels)

    def fill_fraction(self, sid):
        with self._lock:
            return self.buffer.fill_fraction(sid)

    def export_row(self, sid):
        with self._lock:
            return self.buffer.export_row(sid)

    def import_row(self, sid, z, t, label, newest):
        with self._lock:
            self.buffer.import_row(sid, z, t, label, newest)

    def snapshot(self):
        with self._lock:
            return self.buffer.snapshot()

    def refine(self, key):
        if self.refiner is None:
            raise RuntimeError("backend built without a head: no refiner")
        with self._lock:
            z, mask, labels = self.buffer.snapshot()
            self.snapshot_h2d_bytes += (z.nbytes + mask.nbytes
                                        + labels.nbytes
                                        + self.buffer.active.nbytes)
            out = self.refiner.refine_arrays(key, z, mask, labels,
                                             self.buffer.active)
            if self.memory is not None:
                self.memory = self._em(self.memory, z.reshape(-1, self.dim),
                                       weights=mask.reshape(-1))
            return out


def _snapshot_rows(z, t, label, newest, active, *, window):
    """Temporal-order snapshot of a block of session rows, on device.

    Row-local (no cross-session term), so the same function serves the
    global jit snapshot and the per-shard view inside ``shard_map``.
    Same math as ``FleetBuffer.snapshot`` — the parity tests compare the
    two bitwise."""
    w_idx = jnp.arange(window, dtype=newest.dtype)
    order = (newest - window + 1)[:, None] + w_idx[None, :]   # (n, W)
    slots = order % window
    valid = jnp.take_along_axis(t, slots, axis=1) == order
    valid &= (newest >= 0)[:, None] & (active > 0)[:, None]
    zs = jnp.where(valid[:, :, None],
                   jnp.take_along_axis(z, slots[:, :, None], axis=1), 0.0)
    labels = jnp.where(valid, jnp.take_along_axis(label, slots, axis=1), -1)
    return zs, valid.astype(jnp.float32), labels


class ShardedFleetBackend(FleetBackend):
    """Device-resident fleet data plane sharded over a ``sessions`` axis.

    State lives as donated ``jax.Array``s (``z``/``t``/``label``/
    ``newest``/``active``) with dim 0 partitioned over the mesh; ingest is
    a jitted in-place scatter (batch padded to powers of two so the
    compile cache stays O(log capacity)); refine runs one
    ``shard_map``'d step per round — snapshot, hybrid loss, cross-shard
    pmean of loss/parts/grads, optional psum'd distributional-memory
    update — and only scalars + the (N,) per-session losses ever leave
    the device.

    Admission is **least-loaded**: each shard owns a contiguous block of
    rows (``shards_of``), and ``admit`` places the new session on the
    shard with the fewest active sessions (ties break to the lowest
    shard index; within a shard rows hand out lowest-first, exactly the
    host free-list order).  A fleet that fills and drains therefore
    keeps its refine work balanced across the mesh instead of stacking
    every live session on shard 0 (ROADMAP: per-shard load balancing of
    admissions; pinned in ``tests/test_fleet_backend.py``).
    """

    kind = "sharded"
    device_ingest = True

    def __init__(self, *, capacity=32, window=100, dim=128, head_init=None,
                 head_apply=None, cfg: HybridCfg = HybridCfg(), lr=1e-2,
                 seed=0, n_components=0, memory_decay=0.05, mesh=None,
                 axis=SESSIONS_AXIS):
        super().__init__()
        if n_components and head_init is None:
            raise ValueError("fleet memory (n_components) updates ride the "
                             "refine round: pass head_init/head_apply too")
        if mesh is None:
            from repro.launch.mesh import make_sessions_mesh
            mesh = make_sessions_mesh(axis=axis)
        self.mesh, self.axis = mesh, axis
        self.shards = mesh.shape[axis]
        if capacity % self.shards:
            raise ValueError(
                f"capacity={capacity} must divide evenly over "
                f"{self.shards} session shards")
        self.capacity, self.window, self.dim = capacity, window, dim
        self._sharding = sessions_sharding(mesh, axis)
        put = lambda x: jax.device_put(x, self._sharding)
        self.z = put(jnp.zeros((capacity, window, dim), jnp.float32))
        self.t = put(jnp.full((capacity, window), T_SENTINEL_DEV, jnp.int32))
        self.label = put(jnp.full((capacity, window), -1, jnp.int32))
        self.newest = put(jnp.full((capacity,), -1, jnp.int32))
        self.active_dev = put(jnp.zeros((capacity,), jnp.float32))
        # host-side admission bookkeeping: one free-list PER SHARD (each
        # a lowest-row-first stack like FleetBuffer's) + per-shard active
        # counts, so admit can place least-loaded across the mesh
        self._active = np.zeros((capacity,), bool)
        self._dirty = np.zeros((capacity,), bool)
        rows = capacity // self.shards
        self._free_by_shard = [
            list(range((s + 1) * rows - 1, s * rows - 1, -1))
            for s in range(self.shards)]
        self._shard_active = [0] * self.shards
        self.snapshot_h2d_bytes = 0
        self.ingest_h2d_bytes = 0
        self.ingest_d2d_bytes = 0

        # -- compiled state transitions (donated: in-place on device) -------
        def _ins(z, t, label, newest, sids, slots, ts, zs, labels,
                 ts_newest):
            # ts_newest == ts except when insert_batch folded duplicate
            # (sid, slot) writes: the ring keeps the LAST write's frame,
            # newest still advances to the max timestamp seen
            return (z.at[sids, slots].set(zs),
                    t.at[sids, slots].set(ts),
                    label.at[sids, slots].set(labels),
                    newest.at[sids].max(ts_newest))

        def _ins_placed(z, t, label, newest, sid_zl, slots, ts, zs, labels,
                        sid_nw, nw_ts):
            # blocked shard-local scatter (insert_batch_placed): every
            # operand is an equal per-shard block, so under shard_map each
            # device scatters only its own rows.  Rows carrying the DROP
            # sentinel (local sid == rows-per-shard: pads and superseded
            # duplicate writes) fall out of range and mode="drop" makes
            # them no-ops; ``newest`` maxes over ALL real rows, which is
            # order-independent, so duplicates need no fold there.
            return (z.at[sid_zl, slots].set(zs, mode="drop"),
                    t.at[sid_zl, slots].set(ts, mode="drop"),
                    label.at[sid_zl, slots].set(labels, mode="drop"),
                    newest.at[sid_nw].max(nw_ts, mode="drop"))

        def _wipe_admit(z, t, label, newest, active, sid):
            return (z.at[sid].set(0.0),
                    t.at[sid].set(T_SENTINEL_DEV),
                    label.at[sid].set(-1),
                    newest.at[sid].set(-1),
                    active.at[sid].set(1.0))

        def _implant(z, t, label, newest, sid, zr, tr, lr, nw):
            # whole-row set: the migration import seam (export_row's
            # inverse) — one executable regardless of which row
            return (z.at[sid].set(zr),
                    t.at[sid].set(tr),
                    label.at[sid].set(lr),
                    newest.at[sid].set(nw))

        # out_shardings pinned: XLA's scatter sharding propagation would
        # otherwise return replicated rings, silently resharding (and
        # recompiling) the next refine step
        shd = self._sharding
        self._insert_fn = jax.jit(_ins, donate_argnums=(0, 1, 2, 3),
                                  out_shardings=(shd,) * 4)
        pa = P(axis)
        self._insert_placed_fn = jax.jit(
            jax.shard_map(_ins_placed, mesh=mesh, in_specs=(pa,) * 11,
                          out_specs=(pa,) * 4, check_vma=False),
            donate_argnums=(0, 1, 2, 3))
        self._wipe_fn = jax.jit(_wipe_admit, donate_argnums=(0, 1, 2, 3, 4),
                                out_shardings=(shd,) * 5)
        self._implant_fn = jax.jit(_implant, donate_argnums=(0, 1, 2, 3),
                                   out_shardings=(shd,) * 4)
        self._set_active_fn = jax.jit(
            lambda active, sid, v: active.at[sid].set(v),
            donate_argnums=(0,), out_shardings=shd)
        self._snapshot_fn = jax.jit(
            partial(_snapshot_rows, window=window))

        # -- the shard_map'd refine round -----------------------------------
        self.refiner = None
        self.memory = None
        if head_init is not None:
            self.refiner = FleetRefiner(head_init, head_apply, cfg=cfg,
                                        lr=lr, seed=seed)
            # commit head/opt/memory to the mesh-replicated sharding NOW:
            # otherwise the first apply_grads would flip their committed
            # sharding and force one silent refine-step recompile
            replicated = jax.sharding.NamedSharding(mesh, P())
            st = self.refiner.state
            st.params = jax.device_put(st.params, replicated)
            st.opt_state = jax.device_put(st.opt_state, replicated)
            fleet_loss = make_fleet_loss(head_apply, cfg, axis_name=axis,
                                         axis_size=self.shards)
            if n_components:
                self.memory = jax.device_put(
                    gmm.init_gmm(jax.random.PRNGKey(seed + 1),
                                 n_components, dim), replicated)

            def _local(params, key, z, t, label, newest, active):
                zs, mask, labels = _snapshot_rows(z, t, label, newest,
                                                  active, window=window)
                (loss, (losses, parts)), grads = jax.value_and_grad(
                    fleet_loss, has_aux=True)(params, key, zs, mask,
                                              labels, active)
                loss = jax.lax.pmean(loss, axis)
                parts = {k: jax.lax.pmean(v, axis) for k, v in parts.items()}
                grads = pmean_grads(grads, axis)
                return loss, parts, losses, grads, (zs, mask)

            if n_components:
                def local_step(params, mem, key, z, t, label, newest,
                               active):
                    loss, parts, losses, grads, (zs, mask) = _local(
                        params, key, z, t, label, newest, active)
                    mem = gmm.em_update(mem, zs.reshape(-1, dim),
                                        weights=mask.reshape(-1),
                                        decay=memory_decay, axis_name=axis,
                                        reseed_frac=0.0)
                    return loss, parts, losses, grads, mem

                in_specs = (P(), P(), P()) + (P(axis),) * 5
                out_specs = (P(), P(), P(axis), P(), P())
            else:
                def local_step(params, key, z, t, label, newest, active):
                    loss, parts, losses, grads, _ = _local(
                        params, key, z, t, label, newest, active)
                    return loss, parts, losses, grads

                in_specs = (P(), P()) + (P(axis),) * 5
                out_specs = (P(), P(), P(axis), P())

            self._refine_step = jax.jit(jax.shard_map(
                local_step, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False))

    # -- session lifecycle ---------------------------------------------------
    @property
    def n_active(self):
        return int(self._active.sum())

    @property
    def active(self):
        return self._active

    def admit(self):
        """Least-loaded placement: the new session lands on the shard
        with the fewest active sessions (ties -> lowest shard index)."""
        with self._lock:
            ranked = [(self._shard_active[s], s)
                      for s in range(self.shards) if self._free_by_shard[s]]
            if not ranked:
                raise FleetFullError(
                    f"all {self.capacity} session rows in use")
            _, shard = min(ranked)
            sid = self._free_by_shard[shard].pop()
            self._shard_active[shard] += 1
            if self._dirty[sid]:   # deferred O(W·d) wipe, on device
                (self.z, self.t, self.label, self.newest,
                 self.active_dev) = self._wipe_fn(
                    self.z, self.t, self.label, self.newest, self.active_dev,
                    jnp.int32(sid))
                self._dirty[sid] = False
            else:
                self.active_dev = self._set_active_fn(
                    self.active_dev, jnp.int32(sid), jnp.float32(1.0))
            self._active[sid] = True
            return sid

    def evict(self, sid):
        with self._lock:
            if not self._active[sid]:
                raise KeyError(f"session {sid} is not active")
            self._active[sid] = False
            self._dirty[sid] = True
            shard = self.shard_of(sid)
            self._free_by_shard[shard].append(sid)
            self._shard_active[shard] -= 1
            self.active_dev = self._set_active_fn(
                self.active_dev, jnp.int32(sid), jnp.float32(0.0))

    # -- ingest --------------------------------------------------------------
    def insert(self, sid, t, z, label=-1):
        z = z[None] if isinstance(z, jax.Array) else np.asarray(z)[None]
        self.insert_batch(np.array([sid]), np.array([t]), z,
                          np.array([label]))

    def insert_batch(self, sids, ts, zs, labels=None):
        """Donated in-place scatter into the device rings.

        ``zs`` may be a ``jax.Array`` (stays on device, 0 ingest-h2d
        bytes) or a host array (one h2d transfer, counted).  The batch is
        repeat-padded to the next power of two so each batch size bucket
        compiles once (pad rows duplicate entry 0's indices with
        identical values — a well-defined scatter).  Caller-supplied
        duplicate (sid, slot) pairs are folded to numpy's last-wins
        semantics before the scatter, keeping the host-backend parity."""
        with self._lock:
            self._insert_batch_locked(sids, ts, zs, labels)

    def insert_batch_placed(self, sids, ts, zs, labels, rows):
        """Shard-local scatter of a tick batch already blocked per shard.

        The sharded dispatch plane (``StreamSplitGateway`` with
        ``shard_dispatch``) lays each tick's embeddings out as one global
        ``(R, d)`` device array over the sessions axis in equal per-shard
        blocks; ``rows[i]`` names frame ``i``'s global row in that layout,
        and every frame's row must sit inside the block owned by its
        session's shard (checked), so the scatter — a ``shard_map`` over
        the same axis — never moves a payload byte across shards.  Rows
        not named by ``rows`` (pads) and duplicate (sid, slot) writes
        superseded by a later frame scatter with an out-of-range DROP
        sentinel under ``mode="drop"``; ``newest`` still maxes over every
        real row, matching ``insert_batch``'s last-wins + max-ts fold.
        """
        with self._lock:
            sids = as_host(sids, np.int64)
            ts = as_host(ts, np.int64)
            rows = as_host(rows, np.int64)
            if not self._active[sids].all():
                raise KeyError("insert_batch into inactive session")
            n = len(sids)
            if n == 0:
                return
            if not isinstance(zs, jax.Array):
                raise TypeError("insert_batch_placed takes the staged "
                                "device array; host payloads go through "
                                "insert_batch")
            R = int(zs.shape[0])
            if R % self.shards:
                raise ValueError(f"blocked batch of {R} rows does not "
                                 f"split over {self.shards} shards")
            block = R // self.shards
            rows_ps = self.capacity // self.shards
            if int(ts.max()) > np.iinfo(np.int32).max:
                raise ValueError("frame index exceeds the device ring's "
                                 "int32 range; re-key session time or use "
                                 "HostFleetBackend")
            shard = self.shards_of(sids)
            if not np.array_equal(rows // block, shard):
                raise ValueError("frame placed in a row block that is not "
                                 "its session's shard")
            if labels is None:
                labels = np.full(n, -1, np.int64)
            labels32 = as_host(labels, np.int64).astype(np.int32)
            loc = (sids - shard * rows_ps).astype(np.int32)
            slots = np.asarray(ts % self.window, np.int32)
            drop = np.int32(rows_ps)     # out of local range -> no-op
            sid_zl = np.full(R, drop, np.int32)
            slot_b = np.zeros(R, np.int32)
            ts_b = np.zeros(R, np.int32)
            lab_b = np.zeros(R, np.int32)
            sid_nw = np.full(R, drop, np.int32)
            nw_b = np.zeros(R, np.int32)
            keep = np.ones(n, bool)
            keys = sids * self.window + slots
            if len(np.unique(keys)) < n:
                last = {}
                for i, k in enumerate(keys.tolist()):
                    last[k] = i
                keep[:] = False
                keep[np.fromiter(last.values(), np.int64)] = True
            kr = rows[keep]
            sid_zl[kr] = loc[keep]
            slot_b[kr] = slots[keep]
            ts_b[kr] = ts[keep].astype(np.int32)
            lab_b[kr] = labels32[keep]
            sid_nw[rows] = loc
            nw_b[rows] = ts.astype(np.int32)
            self.ingest_d2d_bytes += n * self.dim * 4
            self.z, self.t, self.label, self.newest = self._insert_placed_fn(
                self.z, self.t, self.label, self.newest, sid_zl, slot_b,
                ts_b, zs, lab_b, sid_nw, nw_b)

    def _insert_batch_locked(self, sids, ts, zs, labels):
        sids = as_host(sids, np.int64)
        ts = as_host(ts, np.int64)
        if not self._active[sids].all():
            raise KeyError("insert_batch into inactive session")
        n = len(sids)
        if n == 0:                       # host-buffer contract: a no-op
            return
        if int(ts.max()) > np.iinfo(np.int32).max:
            # the device rings keep int32 frame indices (jax default int
            # width); silently wrapping would drop the session from every
            # refine round while the host backend kept serving it
            raise ValueError("frame index exceeds the device ring's int32 "
                             "range; re-key session time or use "
                             "HostFleetBackend")
        if labels is None:
            labels = np.full(n, -1, np.int64)
        sids32 = np.asarray(sids, np.int32)
        slots32 = np.asarray(ts % self.window, np.int32)
        ts32 = np.asarray(ts, np.int32)
        ts_newest = ts32
        labels32 = as_host(labels, np.int64).astype(np.int32)
        if not isinstance(zs, jax.Array):
            zs = as_host(zs, np.float32)
            self.ingest_h2d_bytes += zs.nbytes
        else:   # staged dispatch path: payload never touches the host
            self.ingest_d2d_bytes += zs.nbytes
        keys = sids32.astype(np.int64) * self.window + slots32
        if len(np.unique(keys)) < n:
            # duplicate (sid, slot) writes in one batch: jnp scatter with
            # repeated indices is undefined, numpy fancy assignment keeps
            # the last — fold to last-wins here (max timestamp per ring
            # slot still reaches ``newest``) so both backends agree
            last, tmax = {}, {}
            for i, k in enumerate(keys.tolist()):
                last[k] = i
                tmax[k] = max(tmax.get(k, ts32[i]), ts32[i])
            keep = np.sort(np.fromiter(last.values(), np.int64))
            sids32, slots32, ts32, labels32 = (
                a[keep] for a in (sids32, slots32, ts32, labels32))
            ts_newest = np.array([tmax[k] for k in keys[keep]], np.int32)
            zs = zs[keep] if isinstance(zs, jax.Array) \
                else np.ascontiguousarray(zs[keep])
            n = len(keep)
        pad = pad_pow2(n) - n
        if pad:
            rep = lambda a: np.concatenate(
                [a, np.broadcast_to(a[:1], (pad,) + a.shape[1:])])
            sids32, slots32, ts32, labels32, ts_newest = map(
                rep, (sids32, slots32, ts32, labels32, ts_newest))
            zs = jnp.concatenate(
                [zs, jnp.broadcast_to(zs[:1], (pad,) + zs.shape[1:])]) \
                if isinstance(zs, jax.Array) else rep(zs)
        self.z, self.t, self.label, self.newest = self._insert_fn(
            self.z, self.t, self.label, self.newest, sids32, slots32,
            ts32, jnp.asarray(zs, jnp.float32), labels32, ts_newest)

    def export_row(self, sid):
        """Device row -> host representation (one D2H per array): int64
        timestamps with the host ``T_SENTINEL`` marking empty slots, so
        the snapshot implants into either backend kind."""
        with self._lock:
            if not self._active[sid]:
                raise KeyError(f"session {sid} is not active")
            z = np.asarray(self.z[sid])
            t32 = np.asarray(self.t[sid])
            t = t32.astype(np.int64)
            t[t32 == T_SENTINEL_DEV] = T_SENTINEL
            label = np.asarray(self.label[sid]).astype(np.int64)
            return z, t, label, int(self.newest[sid])

    def import_row(self, sid, z, t, label, newest):
        with self._lock:
            if not self._active[sid]:
                raise KeyError(f"session {sid} is not active")
            z = as_host(z, np.float32)
            if z.shape != (self.window, self.dim):
                raise ValueError(
                    f"row shape {z.shape} != ({self.window}, {self.dim}) "
                    "— migrating between fleets with different window/dim "
                    "is not supported")
            t = as_host(t, np.int64)
            live = t != T_SENTINEL
            if live.any() and int(t[live].max()) > np.iinfo(np.int32).max:
                raise ValueError("frame index exceeds the device ring's "
                                 "int32 range; re-key session time or use "
                                 "HostFleetBackend")
            t32 = np.where(live, t, T_SENTINEL_DEV).astype(np.int32)
            (self.z, self.t, self.label, self.newest) = self._implant_fn(
                self.z, self.t, self.label, self.newest, jnp.int32(sid),
                jnp.asarray(z), jnp.asarray(t32),
                jnp.asarray(as_host(label, np.int64).astype(np.int32)),
                jnp.int32(newest))
            self.ingest_h2d_bytes += z.nbytes + t32.nbytes

    def fill_fraction(self, sid):
        with self._lock:
            if not self._active[sid]:
                return 0.0
            newest = int(self.newest[sid])
            if newest < 0:
                return 0.0
            order = np.arange(newest - self.window + 1, newest + 1)
            t_row = np.asarray(self.t[sid])
            return float((t_row[order % self.window] == order).mean())

    # -- refinement ----------------------------------------------------------
    def refine(self, key):
        """One fleet-wide step across the session mesh — no fleet
        snapshot ever crosses the host boundary (``snapshot_h2d_bytes``
        stays 0; only scalars and the (N,) per-session losses come back).
        """
        if self.refiner is None:
            raise RuntimeError("backend built without a head: no refiner")
        with self._lock:
            args = (self.refiner.state.params,)
            if self.memory is not None:
                args += (self.memory,)
            out = self._refine_step(*args, key, self.z, self.t, self.label,
                                    self.newest, self.active_dev)
            if self.memory is not None:
                loss, parts, losses, grads, self.memory = out
            else:
                loss, parts, losses, grads = out
            self.refiner.apply_grads(grads)
            return (float(loss), {k: float(v) for k, v in parts.items()},
                    np.asarray(losses))

    # -- observability -------------------------------------------------------
    def snapshot(self):
        """Host copy of the fleet view (observability / compat — NOT the
        refine path, which reads the device rings in place)."""
        with self._lock:
            z, mask, labels = self._snapshot_fn(self.z, self.t, self.label,
                                                self.newest, self.active_dev)
        return (np.asarray(z), np.asarray(mask),
                np.asarray(labels, np.int64))


def make_backend(kind="host", **kw) -> FleetBackend:
    """Backend factory: ``host`` (numpy rings, single device) or
    ``sharded`` (device-resident rings over a ``sessions`` mesh)."""
    if kind == "host":
        kw.pop("mesh", None)
        kw.pop("axis", None)
        return HostFleetBackend(**kw)
    if kind == "sharded":
        return ShardedFleetBackend(**kw)
    raise ValueError(f"unknown fleet backend kind: {kind!r}")
