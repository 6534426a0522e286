"""``StreamSplitGateway`` — THE way to run the StreamSplit pipeline.

One typed surface over what used to be six hand-wired call conventions:

    gw = StreamSplitGateway(enc_cfg, params, policy=make_policy("rule", L))
    info = gw.open_session(platform="pi4", qos=QoSClass.STANDARD)
    gw.submit(info.sid, FrameRequest(t=0, mel=mel, u=0.3, ...))
    results = gw.tick()          # decide -> k-bucketed dispatch -> ingest
    gw.close_session(info.sid)

Internally the gateway owns admission into a ``FleetBackend``, per-tick
**k-bucketed batched split execution**, periodic fleet refinement
rounds, and per-session ``LazySync`` accounting.  The fleet data plane
is pluggable (``backend=``): the default ``HostFleetBackend`` keeps the
session rings in host numpy, while ``ShardedFleetBackend`` keeps them
device-resident and sharded over a ``sessions`` mesh axis, refining the
whole fleet in one ``shard_map`` step (see ``core/fleet_backend.py`` and
``docs/SHARDING.md``).  The serving hot path: every frame whose policy
decision landed on the same split index k rides ONE padded
``SplitEngine`` dispatch (the serving analogue of
``CascadeServer.handle``'s two sub-batches) instead of one ``run()`` per
frame — embeddings stay bit-identical to the per-frame path
(``benchmarks/gateway_serve.py`` measures the speedup and asserts the
bit-parity; ``tests/test_gateway.py`` pins it).

The tick itself is an **overlapped, single-sync data plane**
(docs/PERF.md): the whole tick's frames are staged host→device as ONE
``(B, frames, n_mels)`` transfer, each k-bucket gathers its rows on
device (``jnp.take``) and issues its edge→wire→server chain
asynchronously, and the tick blocks exactly once on the concatenated
embeddings — one device sync and one device→host copy per tick, however
many buckets the policy produced.  ``overlap=False`` restores the PR-3
per-bucket-sync dispatch (the benchmark baseline), and
``tick(profile=True)`` trades the single sync for per-bucket timing.

The overlapped tick is split into two public phases —
``tick_launch() -> TickPlan`` (stage + async dispatch + overlapped host
bookkeeping, never blocks) and ``tick_collect(plan)`` (the one sync +
D2H + delivery) — with ``tick()`` simply composing them.  The streaming
runtime (``repro.serving.StreamServer``, docs/STREAMING.md) exploits the
seam for cross-tick pipelining: tick t+1 launches while tick t's chains
are still in flight, and ``device_syncs_per_tick`` stays 1.

On a multi-device ``ShardedFleetBackend`` the overlapped plane goes one
step further and **shards the dispatch itself** (``shard_dispatch``,
docs/SHARDING.md): each session's frames are staged into the block of a
single sharded H2D transfer owned by its fleet shard (placement was
decided at ``admit`` by the least-loaded free lists), every k-bucket's
edge→wire→server chain executes per device against a per-shard replica
of the encoder weights, per-shard embeddings reassemble into one global
sharded array with zero cross-device copies
(``jax.make_array_from_single_device_arrays``), and the fleet ring
scatter (``insert_batch_placed``) is a ``shard_map`` over the same axis
— so no frame's payload ever crosses a shard boundary and the
one-sync/one-D2H contract survives verbatim at every shard count.

All wall-clock reads go through the injectable ``clock=`` callable
(default ``time.perf_counter``), so latency/uptime numbers in
``FrameResult``/``GatewayStats`` are deterministic under a fake clock in
tests.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.policies import SplitPolicy
from repro.api.types import (AdmissionError, FrameRequest, FrameResult,
                             GatewayStats, QoSClass, SessionInfo,
                             SessionSnapshot)
from repro.core.env import EdgeCloudEnv
from repro.core.fleet import FleetFullError, HostFleetBackend, pad_pow2
from repro.core.splitter import SplitEngine
from repro.core.sync import LazySync, SyncCfg
from repro.obs import MetricsRegistry, Phases, to_prometheus

# The gateway's tick phases (docs/OBSERVABILITY.md): each is a profiler
# span ``gateway.<phase>`` and three ``gateway_phase_*_total`` counters.
PHASES = ("launch", "decide", "stage_h2d", "dispatch", "account",
          "collect", "sync", "refine", "open_session")


class TickPlan:
    """One in-flight overlapped tick, between ``tick_launch`` and
    ``tick_collect``: the launched device chains plus the host context
    needed to deliver their results.  Opaque to callers — the streaming
    runtime (``serving/server.py``) holds at most one while it stages
    the NEXT tick under this one's chains (cross-tick pipelining)."""

    __slots__ = ("pending", "t0", "profile", "launched", "z_all", "t_d0",
                 "syncs", "d2h", "seq", "rowmap", "engine_launches")

    def __init__(self, pending, t0, profile=False, seq=0):
        self.pending = pending     # [(sid, FrameRequest, mel f32)] served
        self.t0 = t0               # clock at tick_launch entry
        self.profile = profile
        self.launched = []         # (k, idx, wire bytes, bucket ms, shard)
        self.z_all = None          # unmaterialized (B, d) device embeddings
        self.t_d0 = t0             # clock at dispatch start
        self.syncs = 0             # launch-phase waits (profile mode only)
        self.d2h = 0
        self.seq = seq             # launch order — collect must match
        self.rowmap = None         # sharded plane: submission idx -> row
        self.engine_launches = 0   # SplitEngine executables this tick

    def __len__(self):
        return len(self.pending)


class _Session:
    """Mutable per-session record (internal — the API hands out frozen
    ``SessionInfo`` snapshots only)."""

    __slots__ = ("sid", "platform", "qos", "sync", "frames", "wire_bytes",
                 "transitions", "last_k")

    def __init__(self, sid, platform, qos, sync_cfg):
        self.sid = sid
        self.platform = platform
        self.qos = qos
        self.sync = LazySync(sync_cfg)
        self.frames = 0
        self.wire_bytes = 0
        self.transitions = 0
        self.last_k = -1


class StreamSplitGateway:
    """Session/gateway layer over the whole edge–cloud pipeline.

    Parameters
    ----------
    enc_cfg, params : the audio encoder config + weights the split engine
        executes (``core/*`` semantics unchanged — the gateway is a
        dispatch layer, not a new model).
    policy : a batched ``SplitPolicy`` (see ``api/policies.py``).
    backend : a ``FleetBackend`` owning the session rings + refinement.
        Defaults to a ``HostFleetBackend`` built from ``capacity`` /
        ``window`` / ``head_init`` / ``head_apply`` / ``refine_lr`` /
        ``seed``; pass a ``ShardedFleetBackend`` to shard the fleet over
        a ``sessions`` mesh (those ctor args are then ignored — the
        backend already owns them).
    capacity, window : fleet dimensions; the server-side temporal rings
        are ``(capacity, window, enc_cfg.d_embed)``.
    head_init, head_apply : optional task head for fleet refinement;
        without them the gateway serves embeddings but never refines.
    refine_every : run one fleet-wide refinement round every this many
        ticks (0 disables).
    qos_reserve : fleet rows held back from BULK (2x) and STANDARD (1x)
        admissions so INTERACTIVE tenants always find room; defaults to
        ``capacity // 8``.
    overlap : serve ticks through the overlapped single-sync data plane
        (default).  ``False`` restores the PR-3 per-bucket-sync dispatch
        — one host staging + device round-trip per k-bucket — kept as
        the measured baseline of ``benchmarks/gateway_serve.py`` and the
        bit-parity reference of ``tests/test_gateway.py``.
    shard_dispatch : run the overlapped plane sharded over the backend's
        ``sessions`` mesh axis — per-device edge→wire→server chains
        co-located with each session's fleet shard, shard-local ring
        scatter, same one-sync/one-D2H contract.  Default ``None``
        auto-enables on a device-resident sharded backend with > 1
        shard; ``True`` forces it (valid on 1 shard too — the bitwise
        parity configuration); ``False`` keeps the single-device plane.
    clock : zero-arg callable returning seconds (default
        ``time.perf_counter``) — every timing stat derives from it.
    """

    def __init__(self, enc_cfg, params, *, policy: SplitPolicy,
                 backend=None, capacity=64, window=100, head_init=None,
                 head_apply=None, refine_every=0, quantize_wire=True,
                 sync_cfg=None, qos_reserve=None, refine_lr=1e-2, seed=0,
                 overlap=True, shard_dispatch=None, clock=time.perf_counter,
                 registry: MetricsRegistry | None = None):
        if policy.L != enc_cfg.n_blocks:
            raise ValueError(
                f"policy action space L={policy.L} != encoder "
                f"n_blocks={enc_cfg.n_blocks}")
        self.cfg = enc_cfg
        self.params = params
        self.policy = policy
        self.engine = SplitEngine(enc_cfg, quantize_wire=quantize_wire)
        if backend is None:
            backend = HostFleetBackend(
                capacity=capacity, window=window, dim=enc_cfg.d_embed,
                head_init=head_init, head_apply=head_apply, lr=refine_lr,
                seed=seed)
        elif backend.dim != enc_cfg.d_embed:
            raise ValueError(
                f"backend dim={backend.dim} != encoder "
                f"d_embed={enc_cfg.d_embed}")
        self.backend = backend
        self.sync_cfg = sync_cfg or SyncCfg()
        self.qos_reserve = (backend.capacity // 8 if qos_reserve is None
                            else qos_reserve)
        self.refine_every = refine_every
        self.overlap = overlap
        if shard_dispatch is None:
            shard_dispatch = bool(
                overlap and backend.device_ingest
                and getattr(backend, "mesh", None) is not None
                and backend.shards > 1)
        if shard_dispatch:
            if not overlap:
                raise ValueError("shard_dispatch shards the overlapped "
                                 "data plane; it needs overlap=True")
            if not (backend.device_ingest
                    and getattr(backend, "mesh", None) is not None):
                raise ValueError(
                    "shard_dispatch co-locates dispatch with fleet shards; "
                    "it needs a device-resident sharded backend "
                    "(ShardedFleetBackend)")
            from repro.distributed.sharding import sessions_sharding
            mesh = backend.mesh
            self._mesh_devices = list(mesh.devices.flat)
            self._staged_sharding = sessions_sharding(mesh, backend.axis)
            # one replica of the encoder weights per dispatch shard,
            # committed once at construction: a per-shard chain whose
            # params already live on its device never pulls a weight
            # byte cross-device at dispatch time
            self._params_by_shard = [jax.device_put(params, d)
                                     for d in self._mesh_devices]
            # an idle shard still owes its (block, d) slice of the global
            # reassembly; zeros blocks are immutable, so one upload per
            # (shard, size) is cached and reused for every idle tick
            self._zeros_blocks = {}
        self.shard_dispatch = shard_dispatch
        self._dispatch_shard_frames = np.zeros(
            backend.shards if shard_dispatch else 1, np.int64)
        self._last_profile = None
        self._clock = clock
        self._t_start = clock()
        self._key = jax.random.PRNGKey(seed)
        self._sessions: dict[int, _Session] = {}
        # (sid, request, validated float32 mel) — converted ONCE at submit
        self._pending: list[tuple[int, FrameRequest, np.ndarray]] = []
        # aggregate counters — live in the shared MetricsRegistry
        # (repro.obs; docs/OBSERVABILITY.md) so GatewayStats is a VIEW
        # over the same objects the hot path mutates and exporters walk
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        R = self.registry
        self._ticks = R.counter("gateway_ticks")
        self._frames = R.counter("gateway_frames")
        self._opened = R.counter("gateway_sessions_opened")
        self._closed = R.counter("gateway_sessions_closed")
        # sessions migrated out/in (repro.cluster)
        self._exported = R.counter("gateway_sessions_exported")
        self._imported = R.counter("gateway_sessions_imported")
        self._refusals = R.counter("gateway_admission_refusals")
        self._dispatches = R.counter("gateway_dispatches")
        self._wire_bytes = R.counter("gateway_wire_bytes")
        self._sync_bytes = R.counter("gateway_sync_bytes")
        self._sync_events = R.counter("gateway_sync_events")
        self._refine_rounds = R.counter("gateway_refine_rounds")
        self._last_refine_loss = float("nan")
        self._last_tick_ms = 0.0
        self._routed = {r: R.counter("gateway_routed_frames", route=r)
                        for r in ("edge", "split", "server")}
        self._shard_frames = np.zeros(backend.shards, np.int64)
        # always-on phase spans and counters; a StreamServer hands the
        # phases its flight recorder for slow_phase events
        self.phases = Phases(R, "gateway", PHASES, clock=clock,
                             tick=self._ticks)
        # the tick span as an EWMA (alpha 0.2): resource_signals feeds
        # it to adaptive policies
        self._tick_ewma = R.gauge("gateway_stage_ewma_ms", stage="tick")
        self._g_last_tick_ms = R.gauge("gateway_last_tick_ms")
        self._g_syncs = R.gauge("gateway_device_syncs_per_tick")
        self._g_d2h = R.gauge("gateway_d2h_copies_per_tick")
        # overlapped data plane instrumentation: every blocking wait and
        # every embedding D2H copy inside tick() goes through _block/_d2h,
        # so the single-sync contract is countable (and pinned by test)
        self._staged_h2d = R.counter("gateway_staged_h2d_bytes")
        self._tick_syncs = 0
        self._tick_d2h = 0
        # split-engine executables launched: the tick's delta of
        # SplitEngine.launches (one per k-bucket on the overlapped plane)
        self._engine_launches = R.counter("gateway_engine_launches")
        self._tick_launches = 0
        # launch/collect sequence numbers: plans MUST collect in launch
        # order (the fleet rings see launch-order scatters) — a
        # violation raises instead of silently corrupting parity
        self._launch_seq = 0
        self._collect_seq = 0

    # -- session lifecycle ---------------------------------------------------
    def _admit_row(self, qos: QoSClass) -> int:
        """QoS-headroom-checked fleet-row admission shared by
        ``open_session`` and ``import_session`` — a migrating session
        obeys the same reserve policy as a fresh one."""
        free = self.backend.capacity - self.backend.n_active
        need = {QoSClass.INTERACTIVE: 1,
                QoSClass.STANDARD: 1 + self.qos_reserve,
                QoSClass.BULK: 1 + 2 * self.qos_reserve}[qos]
        if free < need:
            self._refusals.inc()
            raise AdmissionError(qos, self.backend.n_active,
                                 self.backend.capacity)
        try:
            return self.backend.admit()
        except FleetFullError:
            self._refusals.inc()
            raise AdmissionError(qos, self.backend.n_active,
                                 self.backend.capacity) from None

    def open_session(self, platform="pi4",
                     qos: QoSClass = QoSClass.STANDARD) -> SessionInfo:
        """Admit a session into the fleet; raises ``AdmissionError`` (a
        ``FleetFullError``) when its QoS class finds no headroom."""
        span = self.phases.begin("open_session")
        try:
            sid = self._admit_row(qos)
            self._sessions[sid] = _Session(sid, platform, qos,
                                           self.sync_cfg)
            self._opened.inc()
            return self.session(sid)
        finally:
            self.phases.end(span)

    def session(self, sid) -> SessionInfo:
        s = self._require(sid)
        return SessionInfo(
            sid=s.sid, platform=s.platform, qos=s.qos, frames=s.frames,
            wire_bytes=s.wire_bytes, sync_bytes=s.sync.total_bytes,
            sync_events=len(s.sync.events), transitions=s.transitions,
            last_k=s.last_k, fill_fraction=self.backend.fill_fraction(sid))

    def close_session(self, sid) -> SessionInfo:
        """Evict the session (O(1) — the fleet row is wiped lazily on its
        next admission).  Unserved pending frames are discarded."""
        info = self.session(sid)
        self._pending = [p for p in self._pending if p[0] != sid]
        self.backend.evict(sid)
        del self._sessions[sid]
        self._closed.inc()
        return info

    def _require(self, sid) -> _Session:
        if sid not in self._sessions:
            raise KeyError(f"session {sid} is not open")
        return self._sessions[sid]

    # -- live migration seams (repro.cluster; docs/FEDERATION.md) ------------
    def export_session(self, sid, *, remove: bool = True) -> SessionSnapshot:
        """Freeze everything this session *is* into a ``SessionSnapshot``:
        per-session books, lazy-sync protocol state, and the fleet ring
        row (host representation — implants into any backend kind).

        ``remove=True`` (the migration move) also evicts the row —
        counted in ``sessions_exported``, NOT ``sessions_closed``: the
        stream continues elsewhere.  ``remove=False`` is the
        non-destructive copy the cluster's failure-recovery checkpoints
        use.  Pending (submitted-but-unticked) frames are NOT part of a
        gateway snapshot — tick or discard them first; exporting under
        them raises instead of silently dropping frames."""
        s = self._require(sid)
        if any(p[0] == sid for p in self._pending):
            raise RuntimeError(
                f"session {sid} has pending frames awaiting tick(): a "
                "snapshot taken now would silently drop them — tick "
                "first (the streaming runtime quiesces its pipeline "
                "before exporting)")
        ring_z, ring_t, ring_label, newest = self.backend.export_row(sid)
        snap = SessionSnapshot(
            platform=s.platform, qos=s.qos, frames=s.frames,
            wire_bytes=s.wire_bytes, transitions=s.transitions,
            last_k=s.last_k,
            sync_cfg=s.sync.cfg, sync_last_gmm=s.sync.last_gmm,
            sync_last_weights=s.sync.last_weights,
            sync_total_bytes=s.sync.total_bytes,
            sync_total_energy_j=s.sync.total_energy_j,
            sync_events=tuple(s.sync.events),
            ring_z=ring_z, ring_t=ring_t, ring_label=ring_label,
            ring_newest=newest)
        if remove:
            self.backend.evict(sid)
            del self._sessions[sid]
            self._exported.inc()
        return snap

    def import_session(self, snap: SessionSnapshot) -> SessionInfo:
        """Restore an exported session into THIS gateway: admit a fleet
        row under the same QoS headroom policy as ``open_session``
        (raises ``AdmissionError`` when the class finds no room),
        implant the ring row, and resume the per-session books and
        lazy-sync cadence exactly where the source left them.  The
        session gets a fresh local ``sid`` — cross-gateway identity is
        the cluster's job (``repro.cluster``), not the row index's."""
        sid = self._admit_row(snap.qos)
        s = _Session(sid, snap.platform, snap.qos, snap.sync_cfg)
        s.frames = snap.frames
        s.wire_bytes = snap.wire_bytes
        s.transitions = snap.transitions
        s.last_k = snap.last_k
        s.sync.last_gmm = snap.sync_last_gmm
        s.sync.last_weights = snap.sync_last_weights
        s.sync.total_bytes = snap.sync_total_bytes
        s.sync.total_energy_j = snap.sync_total_energy_j
        s.sync.events = list(snap.sync_events)
        self.backend.import_row(sid, snap.ring_z, snap.ring_t,
                                snap.ring_label, snap.ring_newest)
        self._sessions[sid] = s
        self._imported.inc()
        return self.session(sid)

    # -- ingest --------------------------------------------------------------
    def validate_mel(self, mel) -> np.ndarray:
        """Validate one frame's mel payload and return it as float32.
        THE validation — shared with the streaming runtime
        (``serving/server.py`` runs it on the client's thread) so the
        two surfaces can never drift.  A no-op copy-wise when the input
        is already a float32 ndarray."""
        mel = np.asarray(mel, np.float32)
        if mel.shape != (self.cfg.frames, self.cfg.n_mels):
            raise ValueError(
                f"frame.mel shape {mel.shape} != "
                f"({self.cfg.frames}, {self.cfg.n_mels}) — submit one "
                "unbatched sample per FrameRequest")
        return mel

    def submit(self, sid, frame: FrameRequest) -> None:
        """Queue one frame for the next ``tick``.

        The mel payload is validated AND converted to float32 here, once
        — ``tick`` stages the stored array directly, so no frame is ever
        converted twice (the seed path re-ran ``np.asarray`` per
        dispatch)."""
        self._require(sid)
        self._pending.append((sid, frame, self.validate_mel(frame.mel)))

    def submit_validated(self, sid, frame: FrameRequest) -> None:
        """``submit`` minus the re-validation: ``frame.mel`` MUST
        already be a float32 ndarray of shape (frames, n_mels) — i.e.
        have passed ``validate_mel``.  The streaming runtime validates
        at enqueue time on the client's thread and uses this on the
        serving hot path so no frame is checked twice."""
        self._require(sid)
        self._pending.append((sid, frame, frame.mel))

    # -- the pipeline tick ---------------------------------------------------
    def tick(self, *, profile=False) -> list[FrameResult]:
        """Decide -> k-bucketed batched dispatch -> ingest -> sync ->
        (periodic) refine.  Returns results in submission order.

        On the overlapped plane (``overlap=True``) the dispatch costs one
        staged H2D transfer, one device sync and one D2H embedding copy
        per tick — every bucket's chain runs asynchronously in between.
        ``tick()`` is exactly ``tick_collect(tick_launch())``: the
        streaming runtime (``serving/server.py``) calls the two phases
        separately so tick t+1 can stage and launch while tick t's
        chains are still in flight (cross-tick pipelining).
        ``profile=True`` syncs after each bucket instead, so
        ``FrameResult.latency_ms`` is per-bucket (diagnostics; the tick
        then pays one round-trip per bucket like ``overlap=False``)."""
        if self.overlap:
            return self.tick_collect(self.tick_launch(profile=profile))
        t0 = self._clock()
        pending, self._pending = self._pending, []
        results: list[FrameResult | None] = [None] * len(pending)
        self._tick_dev: list = []     # (bucket idx, device z) per dispatch
        self._tick_syncs = 0
        self._tick_d2h = 0
        n0 = self.engine.launches
        if pending:
            for k, idx in sorted(self._decide(pending).items()):
                self._dispatch(k, idx, pending, results)
            self._ingest(pending, results, now=t0)
        self._tick_launches = self.engine.launches - n0
        self._engine_launches.inc(self._tick_launches)
        self._finish_tick(t0)
        return results  # type: ignore[return-value]

    def tick_launch(self, *, profile=False) -> TickPlan:
        """Launch phase of the overlapped tick: decide, stage the tick's
        mels as ONE H2D transfer, issue every k-bucket's async
        edge→wire→server chain, and run all the host bookkeeping that
        needs no embedding values — WITHOUT ever blocking on the device.

        Returns the in-flight ``TickPlan``; pass it to ``tick_collect``
        to pay the tick's one sync and receive the ``FrameResult``s.
        Between the two calls the chains run on the device, so a caller
        may stage and launch the NEXT tick first — the cross-tick
        pipelining of ``serving.StreamServer``.  At most the launched
        plan's own frames are taken from the pending queue; ``submit``s
        that arrive after the launch ride the next plan."""
        if not self.overlap:
            raise RuntimeError(
                "tick_launch/tick_collect phase the overlapped data plane; "
                "construct the gateway with overlap=True")
        span = self.phases.begin("launch")
        t0 = self._clock()
        pending, self._pending = self._pending, []
        self._tick_syncs = 0
        self._tick_d2h = 0
        plan = TickPlan(pending, t0, profile, seq=self._launch_seq)
        self._launch_seq += 1
        n0 = self.engine.launches
        if pending:
            self._launch_overlapped(plan, self._decide(pending))
        plan.syncs, plan.d2h = self._tick_syncs, self._tick_d2h
        plan.engine_launches = self.engine.launches - n0
        self._engine_launches.inc(plan.engine_launches)
        self.phases.end(span)
        return plan

    def tick_collect(self, plan: TickPlan) -> list[FrameResult]:
        """Collect phase: the tick's ONE device sync + ONE D2H embedding
        copy, ``FrameResult`` delivery in submission order, host-backend
        ingest, tick counters and the periodic refine round.  Plans MUST
        be collected in launch order — the fleet rings already saw the
        launch-order scatters — and out-of-order (or double) collection
        raises instead of silently corrupting parity."""
        if plan.seq != self._collect_seq:
            raise RuntimeError(
                f"tick_collect out of launch order: plan #{plan.seq} "
                f"offered, #{self._collect_seq} expected (plans collect "
                "exactly once, oldest first)")
        self._collect_seq += 1
        span = self.phases.begin("collect")
        # the per-tick sync scoreboard restarts from THIS plan's launch
        # counts: with another tick launched in between (pipelining), the
        # gateway counters were reset by that launch — a collected tick
        # still reports exactly its own waits/copies
        self._tick_syncs, self._tick_d2h = plan.syncs, plan.d2h
        self._tick_launches = plan.engine_launches
        results: list[FrameResult | None] = [None] * len(plan.pending)
        if plan.pending:
            self._collect_overlapped(plan, results)
        self._finish_tick(plan.t0)
        self.phases.end(span)
        return results  # type: ignore[return-value]

    def _decide(self, pending):
        """Policy decision for one tick's pending frames -> {k: [frame
        indices]} buckets.  Bandwidth is normalized exactly like the
        control-plane env so RL policies see the feature scale they were
        trained on."""
        span = self.phases.begin("decide")
        bw_norm = EdgeCloudEnv.BW_NORM
        obs = np.array([[f.u, f.cpu, min(f.bandwidth_mbps / bw_norm, 1.0)]
                        for _, f, _ in pending], np.float32)
        ks = np.clip(np.asarray(self.policy.decide(obs), np.int64),
                     0, self.cfg.n_blocks)
        buckets: dict[int, list[int]] = {}
        for i, k in enumerate(ks):
            buckets.setdefault(int(k), []).append(i)
        self.phases.end(span)
        return buckets

    def _finish_tick(self, t0):
        """Tick epilogue shared by every plane: counters, the periodic
        fleet refine round, the clock-derived tick latency, and the
        always-on EWMA tick-span gauge."""
        self._ticks.inc()
        if (self.backend.can_refine and self.refine_every
                and self._ticks.value % self.refine_every == 0
                and self.backend.n_active):
            span = self.phases.begin("refine")
            key = jax.random.fold_in(self._key, self._refine_rounds.value)
            loss, _, _ = self.backend.refine(key)
            self._refine_rounds.inc()
            self._last_refine_loss = loss
            self.phases.end(span)
        self._last_tick_ms = (self._clock() - t0) * 1e3
        self._g_last_tick_ms.set(self._last_tick_ms)
        self._tick_ewma.ewma(self._last_tick_ms)
        self._g_syncs.set(self._tick_syncs)
        self._g_d2h.set(self._tick_d2h)

    def refine_due_next_tick(self) -> bool:
        """True when the NEXT collected tick will run a fleet refine
        round — the streaming runtime drains its pipeline first so the
        refine sees exactly the frames a sequential gateway would have
        ingested by that tick (``serving/server.py``).  Mirrors
        ``_finish_tick``'s condition exactly, including ``n_active`` —
        an idle fleet never forces a pipeline drain."""
        return bool(self.backend.can_refine and self.refine_every
                    and (self._ticks.value + 1) % self.refine_every == 0
                    and self.backend.n_active)

    # instrumented sync points: every blocking wait and embedding D2H
    # copy in the DISPATCH plane routes through these two, so the
    # single-sync contract is a counted fact
    # (GatewayStats.device_syncs_per_tick / d2h_copies_per_tick), not an
    # assumption.  A periodic backend.refine() blocks on its own loss
    # read and is deliberately outside this scoreboard.
    def _block(self, x):
        self._tick_syncs += 1
        return jax.block_until_ready(x)

    def _d2h(self, x):
        self._tick_d2h += 1
        return np.asarray(x)

    def _launch_overlapped(self, plan, buckets):
        """Launch half of the overlapped tick data plane: ONE staged H2D
        for the whole tick, device-side bucket gathers, async
        edge→wire→server chains, plus everything the host can do without
        the embedding *values* — session/wire counters, lazy-sync
        accounting, and (on a device-resident backend) the fleet ring
        scatter — all issued WITHOUT a sync, so the work hides under the
        in-flight device chains (and, pipelined, under the PREVIOUS
        tick's chains too)."""
        if self.shard_dispatch:
            return self._launch_sharded(plan, buckets)
        pending, profile = plan.pending, plan.profile
        plan.t_d0 = self._clock()
        span = self.phases.begin("stage_h2d")
        # (1) stage the whole tick's frames as ONE host->device transfer,
        # repeat-padded to a pow2 row count: a streaming scheduler ticks
        # at arbitrary batch sizes, and every device-side bucket gather
        # below is compiled against the staged shape — pow2 padding keeps
        # that cache at O(log capacity) executables instead of one per
        # distinct tick size (pad rows are never gathered: bitwise no-op)
        mel_host = np.stack([m for _, _, m in pending])
        pad_rows = pad_pow2(len(pending)) - len(pending)
        if pad_rows:
            mel_host = np.concatenate(
                [mel_host, np.broadcast_to(mel_host[:1], (pad_rows,)
                                           + mel_host.shape[1:])])
        staged = jax.device_put(mel_host)
        self._staged_h2d.inc(mel_host.nbytes)
        self.phases.end(span)
        span = self.phases.begin("dispatch")
        # (2) per-bucket device-side gathers + async dispatch chains
        z_bufs = []
        # frame i -> row in the padded concat; itself pow2-padded (pad
        # entries re-read row 0 and are dropped on the host) so the
        # reassembly gather is also compiled per pow2 size, not per
        # arbitrary streaming tick size
        pos = np.zeros(pad_pow2(len(pending)), np.int32)
        offset = 0
        for k, idx in sorted(buckets.items()):
            t_b = self._clock() if profile else None
            padded = pad_pow2(len(idx))
            gather = np.asarray(idx + idx[:1] * (padded - len(idx)),
                                np.int32)
            mel_b = jnp.take(staged, gather, axis=0)
            z_dev, wire = self.engine.run_batch_async(self.params, mel_b, k)
            ms = None
            if profile:   # diagnostic mode: per-bucket round-trips
                self._block(z_dev)
                ms = (self._clock() - t_b) * 1e3 / len(idx)
            z_bufs.append(z_dev)
            plan.launched.append((k, idx, wire, ms, 0))
            pos[idx] = offset + np.arange(len(idx), dtype=np.int32)
            offset += padded
        # (3) reassemble into submission order ON DEVICE — one gather
        # straight out of the padded concat (drops pad rows + un-buckets
        # in the same op)
        plan.z_all = jnp.take(jnp.concatenate(z_bufs), pos, axis=0)
        self.phases.end(span)
        span = self.phases.begin("account")
        # (4) host bookkeeping + device-resident fleet scatter, all while
        # the chains are still in flight.  The scatter slices z_all to
        # the real row count — one trivial slice executable per distinct
        # tick size, which is the cheapest option: handing the padded
        # array over instead would duplicate (sid, slot) keys and push
        # insert_batch down its duplicate-fold path, whose own gather is
        # per-size too AND pays a host-side fold per tick
        for k, idx, wire, _, s in plan.launched:
            self._account_bucket(k, idx, pending, wire, shard=s)
        if self.backend.device_ingest:
            self._ingest_fleet(pending,            # async device scatter
                               plan.z_all[:len(pending)])
        self._sync_accounting(pending, now=plan.t_d0)
        self.phases.end(span)

    def _launch_sharded(self, plan, buckets):
        """The sharded launch half (``shard_dispatch``): same contract as
        the single-device plane — one staged H2D, async chains, zero
        launch-phase syncs — but laid out over the backend's ``sessions``
        mesh axis so every frame is dispatched ON the device that owns
        its session's fleet shard:

        (1) the tick's frames are grouped by fleet shard into EQUAL
            pow2-padded blocks of one host array and staged with a single
            sharded ``device_put`` — still ONE H2D, each block landing
            shard-local (``plan.rowmap`` remembers submission idx → row);
        (2) each shard's k-buckets gather from their zero-copy local view
            (``addressable_shards``) and run against that shard's
            committed weight replica, so every edge→wire→server chain —
            fused Pallas wire kernel included — executes per device;
        (3) per-shard reassembly gathers restore block order on each
            device and ``make_array_from_single_device_arrays`` binds the
            blocks into one global sharded ``(S·block, d)`` array — no
            cross-device copy, and ``tick_collect`` still pays exactly
            one sync + one D2H on it;
        (4) the fleet scatter goes through ``insert_batch_placed`` — a
            ``shard_map`` over the same axis, so ring ingest never
            crosses a shard either."""
        pending, profile = plan.pending, plan.profile
        plan.t_d0 = self._clock()
        span = self.phases.begin("stage_h2d")
        S = self.backend.shards
        sids = np.fromiter((sid for sid, _, _ in pending), np.int64,
                           len(pending))
        shard = self.backend.shards_of(sids)
        by_shard = [np.flatnonzero(shard == s) for s in range(S)]
        block = pad_pow2(max(1, max(len(b) for b in by_shard)))
        mels = np.stack([m for _, _, m in pending])
        mel_host = np.empty((S * block,) + mels.shape[1:], np.float32)
        rowmap = np.empty(len(pending), np.int64)
        for s, idx_s in enumerate(by_shard):
            base = s * block
            mel_host[base:base + len(idx_s)] = mels[idx_s]
            # pad rows: any real frame's mel — never gathered by a chain,
            # dropped by the placed scatter, so the content is free
            mel_host[base + len(idx_s):base + block] = mels[0]
            rowmap[idx_s] = base + np.arange(len(idx_s))
        staged = jax.device_put(mel_host, self._staged_sharding)
        self._staged_h2d.inc(mel_host.nbytes)
        self.phases.end(span)
        span = self.phases.begin("dispatch")
        by_dev = {sh.device: sh.data for sh in staged.addressable_shards}
        z_blocks = []
        for s in range(S):
            local = by_dev[self._mesh_devices[s]]
            idx_s = by_shard[s]
            if not len(idx_s):
                z = self._zeros_blocks.get((s, block))
                if z is None:
                    z = jax.device_put(
                        np.zeros((block, self.cfg.d_embed), np.float32),
                        self._mesh_devices[s])
                    self._zeros_blocks[(s, block)] = z
                z_blocks.append(z)
                continue
            z_bufs = []
            pos = np.zeros(block, np.int32)
            offset = 0
            for k in sorted(buckets):
                in_shard = [i for i in buckets[k] if shard[i] == s]
                if not in_shard:
                    continue
                t_b = self._clock() if profile else None
                loc = (rowmap[in_shard] - s * block).astype(np.int32)
                padded = pad_pow2(len(loc))
                gather = np.concatenate(
                    [loc, np.broadcast_to(loc[:1], (padded - len(loc),))])
                mel_b = jnp.take(local, gather, axis=0)
                z_dev, wire = self.engine.run_batch_async(
                    self._params_by_shard[s], mel_b, k)
                ms = None
                if profile:   # diagnostic mode: per-chain round-trips
                    self._block(z_dev)
                    ms = (self._clock() - t_b) * 1e3 / len(in_shard)
                z_bufs.append(z_dev)
                plan.launched.append((k, in_shard, wire, ms, s))
                pos[loc] = offset + np.arange(len(loc), dtype=np.int32)
                offset += padded
            z_blocks.append(jnp.take(jnp.concatenate(z_bufs), pos, axis=0))
        plan.z_all = jax.make_array_from_single_device_arrays(
            (S * block, self.cfg.d_embed), self._staged_sharding, z_blocks)
        plan.rowmap = rowmap
        self.phases.end(span)
        span = self.phases.begin("account")
        for k, idx, wire, _, s in plan.launched:
            self._account_bucket(k, idx, pending, wire, shard=s)
        self.backend.insert_batch_placed(
            sids,
            np.fromiter((f.t for _, f, _ in pending), np.int64, len(sids)),
            plan.z_all,
            np.fromiter((f.label for _, f, _ in pending), np.int64,
                        len(sids)),
            rowmap)
        self._shard_frames += np.bincount(shard, minlength=S)
        self._sync_accounting(pending, now=plan.t_d0)
        self.phases.end(span)

    def _collect_overlapped(self, plan, results):
        """Collect half: THE tick's one device sync + one D2H copy, then
        ``FrameResult`` delivery (which needs the host values) and a host
        backend's ring insert.  In profile mode the bucket chains are
        already done, but the reassembly gather still needs its own
        (counted) wait — np.asarray would otherwise block uncounted
        inside ``_d2h``."""
        pending = plan.pending
        span = self.phases.begin("sync")
        z_host = self._d2h(self._block(plan.z_all))
        self.phases.end(span)
        tick_ms = (self._clock() - plan.t_d0) * 1e3 / len(pending)
        if plan.rowmap is not None:
            # sharded plane: un-block the per-shard layout back into
            # submission order (host-side permutation of the ONE copy)
            z_host = z_host[plan.rowmap]
        if not self.backend.device_ingest:
            self._ingest_fleet(pending, z_host[:len(pending)])
        for k, idx, wire, ms, _s in plan.launched:
            route = self._route(k)
            for i in idx:
                sid, req, _ = pending[i]
                results[i] = FrameResult(
                    sid=sid, t=req.t, z=z_host[i], route=route, k=k,
                    wire_bytes=wire,
                    latency_ms=ms if plan.profile else tick_ms,
                    bucket_size=len(idx), shard=_s)
        if plan.profile:
            self._last_profile = self._build_profile(plan)
            for k, ms in self._last_profile["per_bucket_ms"].items():
                self.registry.gauge("gateway_profile_bucket_ms",
                                    k=str(k)).set(ms)

    def _build_profile(self, plan):
        """Fold a profiled plan's per-chain timings into the
        ``last_profile`` dict: per-bucket ms (summed across shards, so
        the field means what it always did) plus per-shard totals —
        frames, chains, total ms and that shard's own per-bucket split —
        so cross-shard skew is visible without a profiler."""
        per_bucket: dict[int, float] = {}
        per_shard: dict[int, dict] = {}
        for k, idx, _wire, ms, s in plan.launched:
            total = (ms or 0.0) * len(idx)
            per_bucket[k] = per_bucket.get(k, 0.0) + total
            ps = per_shard.setdefault(
                s, {"frames": 0, "chains": 0, "ms": 0.0,
                    "per_bucket_ms": {}})
            ps["frames"] += len(idx)
            ps["chains"] += 1
            ps["ms"] += total
            ps["per_bucket_ms"][k] = ps["per_bucket_ms"].get(k, 0.0) + total
        return {"per_bucket_ms": per_bucket, "per_shard": per_shard}

    @property
    def last_profile(self):
        """Per-bucket AND per-shard stage timings of the most recent
        ``tick(profile=True)`` on the overlapped plane (``None`` until
        one runs).  Shape: ``{"per_bucket_ms": {k: ms}, "per_shard":
        {shard: {"frames", "chains", "ms", "per_bucket_ms"}}}`` — the
        single-device plane reports everything under shard 0."""
        return self._last_profile

    def _route(self, k):
        return ("edge" if k >= self.cfg.n_blocks
                else "server" if k == 0 else "split")

    def _account_bucket(self, k, idx, pending, wire, shard=0):
        """Per-bucket serving counters + per-session accounting (pure
        host state — needs no embedding values, so the overlapped plane
        runs it under the in-flight dispatches; the PR-3 path shares it
        so the two planes can never drift apart in what they report).
        On the sharded plane each (shard, k) chain is one dispatch;
        ``shard`` feeds the per-shard dispatch counters."""
        route = self._route(k)
        self._dispatches.inc()
        self._frames.inc(len(idx))
        self._wire_bytes.inc(wire * len(idx))
        self._routed[route].inc(len(idx))
        self._dispatch_shard_frames[shard] += len(idx)
        for i in idx:
            sid = pending[i][0]
            s = self._sessions[sid]
            if s.last_k >= 0 and k != s.last_k:
                s.transitions += 1
            s.last_k = k
            s.frames += 1
            s.wire_bytes += wire

    def _dispatch(self, k, idx, pending, results):
        """The PR-3 per-bucket-sync dispatch (``overlap=False``): host
        staging, one ``run_batch``, one blocking round-trip — per bucket.
        Kept behaviorally identical to PR 3 as the measured baseline +
        bit-parity reference (it shares ``_account_bucket`` with the
        overlapped plane so the two can never drift in what they
        report)."""
        t0 = self._clock()
        mel = np.stack([pending[i][2] for i in idx])
        pad = pad_pow2(len(idx))
        if pad > len(idx):   # repeat-pad: shape buckets stay compiled
            mel = np.concatenate(
                [mel, np.broadcast_to(mel[:1], (pad - len(idx),)
                                      + mel.shape[1:])])
        z_dev, wire = self.engine.run_batch(self.params, mel, k)
        if self.backend.device_ingest:   # fleet ingest skips the host hop
            self._tick_dev.append((idx, z_dev[:len(idx)]))
        z = self._d2h(self._block(z_dev))[:len(idx)]
        ms = (self._clock() - t0) * 1e3 / len(idx)
        self._account_bucket(k, idx, pending, wire)
        route = self._route(k)
        for j, i in enumerate(idx):
            sid, req, _ = pending[i]
            results[i] = FrameResult(
                sid=sid, t=req.t, z=z[j], route=route, k=k,
                wire_bytes=wire, latency_ms=ms, bucket_size=len(idx))

    def _ingest_fleet(self, pending, zs):
        """Fleet-backend ingest of the tick's submission-ordered
        embeddings.  On a device-resident backend ``zs`` is the
        ``jax.Array`` the dispatches produced — the payload flows
        dispatch → rings without ever touching the host (the host copy
        in ``results`` exists only for the clients); on a host backend
        it is the host copy the tick already made."""
        sids = np.array([sid for sid, _, _ in pending], np.int64)
        ts = np.array([f.t for _, f, _ in pending], np.int64)
        labels = np.array([f.label for _, f, _ in pending], np.int64)
        self.backend.insert_batch(sids, ts, zs, labels)
        self._shard_frames += np.bincount(
            self.backend.shards_of(sids), minlength=self.backend.shards)

    def _sync_accounting(self, pending, now=0.0):
        """Per-session lazy-sync protocol accounting (host state only —
        the overlapped plane runs it under the in-flight dispatches).
        ``now`` is the tick's dispatch timestamp from the injected
        ``clock=``, stamped onto every emitted ``SyncEvent.at_s`` so sync
        timelines stay deterministic under a fake clock."""
        for sid, req, _ in pending:
            s = self._sessions[sid]
            for ev in s.sync.on_frame(req.t, charging=req.charging,
                                      bandwidth_mbps=req.bandwidth_mbps,
                                      now=now):
                self._sync_bytes.inc(ev.bytes)
                self._sync_events.inc()

    def _ingest(self, pending, results, now=0.0):
        """The PR-3 composite ingest (``overlap=False`` only): reassemble
        the per-dispatch device slices into submission order, insert,
        then run lazy-sync accounting."""
        if self.backend.device_ingest:
            order = np.concatenate(
                [np.asarray(idx) for idx, _ in self._tick_dev])
            zs = jnp.concatenate([z for _, z in self._tick_dev])[
                np.argsort(order)]
        else:
            zs = np.stack([r.z for r in results])
        self._ingest_fleet(pending, zs)
        self._sync_accounting(pending, now=now)

    # -- observability -------------------------------------------------------
    @property
    def clock(self):
        """The injected timing source (``clock=``) — the serving runtime
        defaults to it so one fake clock drives the whole stack."""
        return self._clock

    @property
    def ticks(self) -> int:
        """Collected-tick count (a launched-but-uncollected ``TickPlan``
        is not a tick yet)."""
        return self._ticks.value

    def stats(self) -> GatewayStats:
        """The gateway scoreboard as a frozen view over the registry —
        every counter field reads the same live metric the hot path
        mutates, so the numbers exporters scrape and the numbers this
        dataclass reports can never drift."""
        # per-shard frame gauges are synced lazily here (stats/export
        # time), not per tick: the numpy arrays ARE the hot-path
        # accumulators and a per-tick loop over shards would tax the
        # S=1 common case for nothing
        for s, v in enumerate(self._shard_frames):
            self.registry.gauge("gateway_shard_frames",
                                shard=str(s)).set(int(v))
        for s, v in enumerate(self._dispatch_shard_frames):
            self.registry.gauge("gateway_dispatch_shard_frames",
                                shard=str(s)).set(int(v))
        return GatewayStats(
            ticks=self._ticks.value, frames=self._frames.value,
            sessions_open=len(self._sessions),
            sessions_opened=self._opened.value,
            sessions_closed=self._closed.value,
            admission_refusals=self._refusals.value,
            dispatches=self._dispatches.value,
            wire_bytes=self._wire_bytes.value,
            sync_bytes=self._sync_bytes.value,
            sync_events=self._sync_events.value,
            refine_rounds=self._refine_rounds.value,
            last_refine_loss=self._last_refine_loss,
            routed={r: c.value for r, c in self._routed.items()},
            backend=self.backend.kind, shards=self.backend.shards,
            shard_frames=tuple(int(v) for v in self._shard_frames),
            dispatch_shards=(self.backend.shards if self.shard_dispatch
                             else 1),
            dispatch_shard_frames=tuple(
                int(v) for v in self._dispatch_shard_frames),
            snapshot_h2d_bytes=self.backend.snapshot_h2d_bytes,
            ingest_h2d_bytes=self.backend.ingest_h2d_bytes,
            device_syncs_per_tick=self._tick_syncs,
            d2h_copies_per_tick=self._tick_d2h,
            engine_launches_per_tick=self._tick_launches,
            staged_h2d_bytes=self._staged_h2d.value,
            uptime_s=self._clock() - self._t_start,
            last_tick_ms=self._last_tick_ms,
            sessions_exported=self._exported.value,
            sessions_imported=self._imported.value,
            phases=self.phases.view())

    def metrics(self) -> str:
        """The gateway's registry in Prometheus text exposition format
        (``repro.obs.export``; docs/OBSERVABILITY.md).  When the
        gateway runs under a ``StreamServer`` the registry is shared, so
        the server's ``metrics()`` supersedes this one."""
        self.stats()                 # sync the lazy per-shard gauges
        return to_prometheus(self.registry)
