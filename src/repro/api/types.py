"""Typed request/response surface of the StreamSplit gateway.

One pipeline, one vocabulary: a client session ``submit``s
``FrameRequest``s, the gateway ``tick`` turns them into ``FrameResult``s
(embedding, route, split index, wire bytes, dispatch latency), and the
aggregate state of the serving plane is a ``GatewayStats``.  Everything
here is a frozen dataclass — values cross the API boundary, never shared
mutable state.
"""
from __future__ import annotations

import pickle
from dataclasses import dataclass, field
from enum import Enum

import numpy as np

from repro.core.fleet import FleetFullError
from repro.core.sync import SyncCfg, SyncEvent


class QoSClass(Enum):
    """Admission class of a session (ROADMAP: load-aware placement).

    ``INTERACTIVE`` sessions may use every fleet row; ``STANDARD`` and
    ``BULK`` are refused progressively earlier so that headroom remains
    for latency-sensitive tenants (see ``StreamSplitGateway.open_session``).
    """

    INTERACTIVE = "interactive"
    STANDARD = "standard"
    BULK = "bulk"


class AdmissionError(FleetFullError):
    """Typed admission failure of ``open_session``.

    Subclasses ``FleetFullError`` so callers already guarding the raw
    fleet keep working; carries the admission context the raw error
    lacks.  ``qos`` is the class that was refused; ``n_active`` /
    ``capacity`` describe the fleet at refusal time.
    """

    def __init__(self, qos: QoSClass, n_active: int, capacity: int):
        self.qos = qos
        self.n_active = n_active
        self.capacity = capacity
        super().__init__(
            f"admission refused for {qos.value} session: "
            f"{n_active}/{capacity} fleet rows in use")


class ClusterDegradedError(RuntimeError):
    """Typed degraded-mode refusal of a ``GatewayCluster``
    (``repro.cluster``; docs/FEDERATION.md): live capacity fell below
    the configured watermark, so new sessions are refused and BULK
    frames are shed at the door — the surviving members' headroom is
    reserved for the streams they already hold.  Counted
    (``ClusterStats.rejected_degraded``), never silent; the refused
    work never enters ``submitted``, so conservation is untouched."""

    def __init__(self, live: int, expected: int, watermark: float,
                 what: str = "admission"):
        self.live = live
        self.expected = expected
        self.watermark = watermark
        super().__init__(
            f"cluster degraded: {live}/{expected} members live "
            f"(watermark {watermark:.2f}) — {what} refused until "
            "capacity recovers")


class ClusterDrainTimeout(RuntimeError):
    """Typed drain-stall summary of ``GatewayCluster.stop(drain=True)``:
    the step budget ran out with frames still outstanding.  ``stragglers``
    maps each stuck session's global sid to its outstanding frame count
    (submitted but neither served, shed, nor counted lost) — before
    this error a stalled drain exited only through an untyped pump
    failure with no record of WHICH streams were stuck."""

    def __init__(self, stragglers: dict, steps: int):
        self.stragglers = dict(stragglers)
        self.steps = steps
        super().__init__(
            f"cluster drain stalled after {steps} steps: "
            f"{len(self.stragglers)} session(s) still hold "
            f"{sum(self.stragglers.values())} outstanding frame(s) "
            f"(gsids {sorted(self.stragglers)})")


@dataclass(frozen=True)
class FrameRequest:
    """One client frame: the mel payload plus the client-side telemetry
    the split policy consumes.

    ``t`` is the session-local absolute frame index (the temporal-buffer
    key — gaps in ``t`` become gap-mask zeros on the server).  ``u`` /
    ``cpu`` are normalized to [0, 1] like the control-plane observation
    ``s_t = [U_t, R_cpu, B_net]``; ``bandwidth_mbps`` is raw so the lazy
    sync protocol can apply its Wi-Fi threshold.
    """

    t: int
    mel: np.ndarray            # (frames, n_mels) — one sample, no batch dim
    label: int = -1
    u: float = 0.5             # GMM-entropy uncertainty U_t
    cpu: float = 0.25          # edge CPU load fraction
    bandwidth_mbps: float = 10.0
    charging: bool = False     # lazy-sync weight-push eligibility


@dataclass(frozen=True)
class FrameResult:
    """What came back for one frame after the tick's bucketed dispatch."""

    sid: int
    t: int
    z: np.ndarray              # (d_embed,) l2-normalized embedding
    route: str                 # "edge" (k=L) | "server" (k=0) | "split"
    k: int                     # split index the policy chose
    wire_bytes: int            # synchronous split-link payload (0 at k=L)
    # dispatch wall-clock per frame.  On the overlapped data plane the
    # tick is one staged H2D + async bucket chains + ONE sync, so this is
    # the measured per-TICK figure (tick dispatch time / frames served);
    # ``tick(profile=True)`` restores per-bucket timing (one sync per
    # bucket — a diagnostic mode, not the serving path).
    latency_ms: float
    bucket_size: int           # how many frames shared this dispatch
    shard: int = 0             # dispatch shard that ran this frame's
    #                            chain (0 on the unsharded plane) — also
    #                            stamped on the frame's trace span


@dataclass(frozen=True)
class SessionInfo:
    """Point-in-time snapshot of one session (returned by ``open_session``,
    ``session`` and ``close_session`` — never live state)."""

    sid: int
    platform: str
    qos: QoSClass
    frames: int                # frames served through the gateway
    wire_bytes: int            # cumulative split-link bytes
    sync_bytes: int            # cumulative lazy-sync downlink bytes
    sync_events: int
    transitions: int           # split-index changes (atomic transitions)
    last_k: int                # -1 before the first served frame
    fill_fraction: float       # of the server-side temporal ring


@dataclass(frozen=True)
class QueuedFrameSnapshot:
    """One queued-but-unserved frame inside a ``SessionSnapshot`` —
    enough to re-enqueue it on another gateway with its ORIGINAL arrival
    time and deadline (migration must not grant waiting frames a fresh
    deadline budget, nor steal the wait they already paid)."""

    frame: FrameRequest
    enq_s: float               # original submit time (caller clock)
    deadline_s: float          # original deadline — survives migration
    preemptions: int = 0
    promoted: bool = False
    weight: float = 1.0
    # the frame's live FrameTrace (repro.obs.trace) when it is sampled —
    # the span itself migrates, so a trace begun on the source member
    # continues seamlessly on the target (None when tracing is off)
    trace: object = None


@dataclass(frozen=True)
class ServerSessionSnapshot:
    """The streaming-runtime half of a ``SessionSnapshot``: per-session
    conservation books, fair-share weight, token-bucket level, and every
    frame still waiting in the QoS queues (oldest first)."""

    submitted: int             # frames accepted into the queues
    served: int                # frames delivered as FrameResults
    shed: int                  # frames visibly shed past the horizon
    weight: float              # STANDARD DRR fair-share weight
    # (rate_per_s, burst, tokens, last_refill_s) or None — the bucket
    # level migrates so a rate-limited tenant cannot reset its budget by
    # riding a rebalance
    bucket: tuple | None = None
    queued: tuple = ()         # QueuedFrameSnapshot, oldest first


@dataclass(frozen=True)
class SessionSnapshot:
    """Everything one session *is*, frozen and serializable — the unit
    of live migration between gateways (``repro.cluster``;
    docs/FEDERATION.md).

    Three layers: the gateway's per-session books (frames, wire bytes,
    split transitions, last k), the lazy-sync protocol counters
    (``core/sync.py`` — cadence state plus emitted events, so the
    downlink timeline continues instead of restarting), and the fleet
    ring row (``(W, d)`` embeddings + timestamps + labels + newest, in
    the host representation so a row exported from any ``FleetBackend``
    implants into any other).  ``server`` carries the streaming
    runtime's half when the session was exported from a ``StreamServer``
    (None from a bare gateway).  Restoring a snapshot onto a fresh
    gateway and replaying the same admitted schedule reproduces every
    embedding and refine loss bit-for-bit (``tests/test_cluster.py``'s
    sequential-replay oracle)."""

    platform: str
    qos: QoSClass
    # gateway per-session books
    frames: int
    wire_bytes: int
    transitions: int
    last_k: int
    # lazy-sync protocol state (core/sync.py)
    sync_cfg: SyncCfg
    sync_last_gmm: int
    sync_last_weights: int
    sync_total_bytes: int
    sync_total_energy_j: float
    sync_events: tuple         # emitted SyncEvents, oldest first
    # fleet ring row (host representation; see FleetBackend.export_row)
    ring_z: np.ndarray         # (W, d) float32
    ring_t: np.ndarray         # (W,) int64, T_SENTINEL marks empty slots
    ring_label: np.ndarray     # (W,) int64
    ring_newest: int
    server: ServerSessionSnapshot | None = None
    version: int = 1

    def to_bytes(self) -> bytes:
        """Wire form of the migration transfer (also what the cluster
        meters as ``ClusterStats.migrated_bytes``)."""
        return pickle.dumps(self, protocol=pickle.HIGHEST_PROTOCOL)

    @staticmethod
    def from_bytes(payload: bytes) -> "SessionSnapshot":
        snap = pickle.loads(payload)
        if not isinstance(snap, SessionSnapshot):
            raise TypeError("payload is not a SessionSnapshot")
        return snap

    @property
    def nbytes(self) -> int:
        return len(self.to_bytes())


@dataclass(frozen=True)
class GatewayStats:
    """Aggregate serving-plane counters (one pipeline, one scoreboard)."""

    ticks: int
    frames: int
    sessions_open: int
    sessions_opened: int
    sessions_closed: int
    admission_refusals: int
    dispatches: int            # k-bucket SplitEngine dispatches issued
    wire_bytes: int
    sync_bytes: int            # lazy-sync downlink across all sessions
    sync_events: int
    refine_rounds: int
    last_refine_loss: float    # nan before the first round
    routed: dict               # route -> frame count ("edge"/"split"/"server")
    # fleet-backend data plane (host vs device-resident sharded)
    backend: str = "host"      # FleetBackend.kind
    shards: int = 1            # session mesh-axis size (1 on host backend)
    shard_frames: tuple = ()   # frames ingested per session shard
    # sharded dispatch plane (StreamSplitGateway shard_dispatch=True,
    # docs/SHARDING.md): the per-tick edge→wire→server chains themselves
    # run per device, co-located with each session's fleet shard
    dispatch_shards: int = 1   # devices the tick dispatch spreads over
    dispatch_shard_frames: tuple = ()  # frames dispatched per shard
    snapshot_h2d_bytes: int = 0  # fleet snapshot bytes copied per refine
    ingest_h2d_bytes: int = 0  # frame payload bytes moved host->device
    # overlapped tick data plane (docs/PERF.md): the dispatch chain is
    # issued asynchronously and synced ONCE per tick, so a mixed-k tick
    # costs one device round-trip regardless of bucket count.  Both
    # counters cover the DISPATCH plane only — a periodic refine round
    # blocks on its own loss read outside this scoreboard.
    device_syncs_per_tick: int = 0   # dispatch-plane waits, last tick
    d2h_copies_per_tick: int = 0     # embedding D2H copies, last tick
    staged_h2d_bytes: int = 0  # cumulative mel bytes staged host->device
    # deterministic under an injected clock= (see StreamSplitGateway)
    uptime_s: float = 0.0      # clock() - clock() at construction
    # wall-clock of the most recent tick, launch -> collect.  Under the
    # streaming runtime's cross-tick pipelining this span deliberately
    # INCLUDES the next tick's interleaved staging/launch — it is the
    # tick's in-flight lifetime, not its exclusive compute cost.
    last_tick_ms: float = 0.0
    # live-migration seams (repro.cluster): sessions that left/arrived
    # via export_session/import_session — distinct from opened/closed, a
    # migration is neither an admission decision nor a client departure
    sessions_exported: int = 0
    sessions_imported: int = 0
    # always-on tick phases (repro.obs.Phases; docs/OBSERVABILITY.md):
    # span name -> (calls, seconds, slow calls), cumulative, so two
    # snapshots give any window
    phases: dict = field(default_factory=dict)

    @property
    def frames_per_dispatch(self) -> float:
        """The batching win: 1.0 is the per-frame loop; N/buckets when
        k-bucketing collapses a tick into few dispatches."""
        return self.frames / self.dispatches if self.dispatches else 0.0


@dataclass(frozen=True)
class StreamStats:
    """Point-in-time scoreboard of the streaming serving runtime
    (``repro.serving.StreamServer``; docs/STREAMING.md).

    Every per-class dict is keyed by the ``QoSClass.value`` strings
    (``"interactive"``/``"standard"``/``"bulk"``) so the whole snapshot
    is JSON-serializable as-is (``benchmarks/stream_serve.py`` writes
    it).  Conservation is an invariant, not a hope: per class,
    ``frames_submitted == frames_served + queue_depth + in_flight
    + shed_expired`` at every snapshot, and ``preempted == requeued``
    always — a preempted frame goes back to the front of its queue; a
    frame only ever leaves the system as a served ``FrameResult`` or as
    a *counted* shed (deadline expired past the configured horizon),
    never silently.  Frames refused at submit raise a typed error and
    count WITHOUT entering ``frames_submitted``: ``QueueFullError`` →
    ``rejected_full`` (bounded queue) and ``RateLimitError`` →
    ``rejected_rate_limited`` (per-session token bucket).
    """

    running: bool              # serving thread alive right now
    ticks: int                 # ticks the runtime has collected
    pipelined_ticks: int       # launched while the previous tick's chains
    #                            were still in flight (cross-tick overlap)
    frames_submitted: dict     # class -> frames accepted into the queues
    frames_served: dict        # class -> frames delivered as FrameResults
    queue_depth: dict          # class -> frames waiting (queued + staged)
    in_flight: dict            # class -> frames launched, not yet collected
    rejected_full: dict        # class -> bounded-queue refusals at submit
    rejected_rate_limited: dict  # class -> token-bucket refusals at submit
    preempted: dict            # class -> frames bumped from a staged tick
    requeued: dict             # class -> preempted frames put back (== preempted)
    shed_expired: dict         # class -> frames dropped visibly: deadline
    #                            expired past SchedulerCfg.shed_horizon_ms
    promoted: dict             # class -> frames staged via the aging lane
    #                            (waited past SchedulerCfg.max_wait_ms)
    deadline_misses: dict      # class -> frames admitted past their deadline
    #                            PLUS shed frames (starved-in-queue misses
    #                            are counted at shed time, not hidden)
    queue_wait_ms: dict        # class -> {"p50","p95","mean","max"} wait
    #                            between submit and tick admission (shed
    #                            frames sample their terminal wait too)
    gateway: GatewayStats      # the dispatch-plane scoreboard underneath
    # the serving loop's phases, shaped like GatewayStats.phases
    phases: dict = field(default_factory=dict)


@dataclass(frozen=True)
class ClusterStats:
    """Cluster-wide scoreboard of a ``GatewayCluster``
    (``repro.cluster``; docs/FEDERATION.md).

    Per-class dicts are keyed by ``QoSClass.value`` strings, like
    ``StreamStats``.  The cluster keeps its OWN conservation books at
    the federation boundary — ``submitted`` counts accepted
    ``GatewayCluster.submit`` calls, ``served``/``shed_expired`` count
    delivery/shed callbacks — so the invariant survives member death
    (a dead member's counters are unreadable; the frames it held are
    never silently forgotten, they land in ``lost_in_flight``):

        submitted == served + queue_depth + in_flight
                     + shed_expired + lost_in_flight      (per class)

    at every snapshot, where ``queue_depth``/``in_flight`` sum over the
    LIVE members.  ``conserved`` checks it.
    """

    members: tuple             # live member names, routing order
    sessions_open: int
    submitted: dict            # class -> frames accepted by the cluster
    served: dict               # class -> FrameResults delivered
    queue_depth: dict          # class -> waiting frames over live members
    in_flight: dict            # class -> launched-not-collected frames
    shed_expired: dict         # class -> visible sheds (cluster-tracked)
    lost_in_flight: dict       # class -> frames lost to member failure —
    #                            explicitly counted, never silent
    rejected_full: dict        # class -> bounded-queue refusals
    rejected_rate_limited: dict  # class -> token-bucket refusals
    migrations: int            # sessions moved between members
    migrated_frames: int       # queued frames replayed on a new owner
    migrated_bytes: int        # serialized SessionSnapshot payload bytes
    migration_pause_ms: dict   # {"p50","p95","max"} per-session pause
    drains: int                # completed drain() calls
    failures: int              # members lost and recovered from
    ring_share: dict           # member -> owned fraction of hash space
    member_stats: dict         # member -> StreamStats (live members)
    # self-healing federation (PR 9; cluster/{replication,health}.py):
    degraded: bool = False     # live capacity below the watermark NOW
    failovers: int = 0         # sessions restored onto a survivor
    retries: int = 0           # transient member faults retried away
    replayed_frames: int = 0   # journal entries re-queued by failovers
    journal_bytes: int = 0     # bytes shipped over the owner->buddy seam
    rejected_degraded: dict = field(default_factory=dict)
    #                            class -> degraded-mode door refusals
    #                            (not in ``submitted``, like other rejects)
    drain_stragglers: int = 0  # sessions stuck at a stop(drain=True)
    #                            timeout (see ClusterDrainTimeout)

    @property
    def conserved(self) -> bool:
        """The cluster-wide per-class conservation identity."""
        return all(
            self.submitted[c] == self.served[c] + self.queue_depth[c]
            + self.in_flight[c] + self.shed_expired[c]
            + self.lost_in_flight[c]
            for c in (q.value for q in QoSClass))


@dataclass(frozen=True)
class ResourceSignals:
    """The serving plane's resource state as a control-plane
    observation (``StreamServer.resource_signals()``;
    docs/OBSERVABILITY.md).

    This is the view the paper's RL splitter needs beside embedding
    ambiguity — "real-time resource monitoring" (PAPER.md §1) — and the
    view the ROADMAP's open autoscaler item is blocked on.  Everything
    is derived from the metrics registry at call time: queue pressure
    (depth over capacity), tail latency (p95 admission wait + the
    always-on EWMA stage timings), and loss pressure (shed/reject
    fraction of recent submissions).  ``as_observation()`` flattens to
    a normalized float vector shaped like the existing ``SplitPolicy``
    observation convention (each component in [0, 1] or clamped there).
    """

    queue_depth: int           # frames waiting across all classes
    queue_fill: float          # depth / total capacity, in [0, 1]
    in_flight: int             # frames launched, not yet collected
    wait_p95_ms: float         # p95 submit->admit wait (sketch)
    stage_ewma_ms: float       # EWMA tick span, launch to collect
    shed_rate: float           # shed / submitted (cumulative), [0, 1]
    reject_rate: float         # refused / offered at the door, [0, 1]
    throughput_fps: float      # frames served per second of uptime

    def as_observation(self) -> "np.ndarray":
        """Normalized float32 vector for a ``SplitPolicy``: load,
        latency (saturating at 1s), and loss pressure."""
        return np.asarray(
            [min(1.0, max(0.0, self.queue_fill)),
             min(1.0, self.wait_p95_ms / 1e3),
             min(1.0, self.stage_ewma_ms / 1e3),
             min(1.0, max(0.0, self.shed_rate)),
             min(1.0, max(0.0, self.reject_rate))],
            dtype=np.float32)
