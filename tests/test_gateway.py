"""Gateway API: session lifecycle + typed admission, k-bucket dispatch
bit-parity with per-frame SplitEngine.run, wire accounting, policy
unification, and refine cadence."""
import jax
import numpy as np
import pytest

from repro.api import (AdmissionError, FrameRequest, QoSClass, SplitPolicy,
                       StreamSplitGateway, make_policy)
from repro.api.policies import (EntropyThresholdPolicy, FixedKPolicy,
                                RLPolicy, RulePolicy)
from repro.core.fleet import FleetFullError
from repro.core.splitter import SplitEngine
from repro.models.audio_encoder import (AudioEncCfg, boundary_bytes,
                                        init_audio_encoder)

CFG = AudioEncCfg(widths=(16, 16, 32, 32), strides=(1, 2, 1, 2),
                  n_mels=32, frames=40, d_embed=32, groups=4)
L = CFG.n_blocks
N_CLASSES = 4


@pytest.fixture(scope="module")
def params():
    return init_audio_encoder(CFG, jax.random.PRNGKey(0))


def _mel(rng):
    return rng.normal(size=(CFG.frames, CFG.n_mels)).astype(np.float32)


def _head():
    def head_init(key):
        return {"w": 0.01 * jax.random.normal(key, (CFG.d_embed, N_CLASSES))}

    def head_apply(p, z):
        return z @ p["w"]

    return head_init, head_apply


class SpreadPolicy:
    """Deterministic test policy: frame i gets k = i % (L+1) — every
    split index appears in one tick."""

    def __init__(self, L):
        self.L = L

    def decide(self, obs_batch):
        return np.arange(len(obs_batch), dtype=np.int64) % (self.L + 1)


# ---------------------------------------------------------------------------
# Session lifecycle + typed admission
# ---------------------------------------------------------------------------

def test_session_lifecycle(params):
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 2),
                            capacity=4, window=8, qos_reserve=0)
    rng = np.random.default_rng(0)
    info = gw.open_session(platform="m2", qos=QoSClass.INTERACTIVE)
    assert info.platform == "m2" and info.qos is QoSClass.INTERACTIVE
    assert info.frames == 0 and info.last_k == -1
    gw.submit(info.sid, FrameRequest(t=0, mel=_mel(rng), label=1))
    (r,) = gw.tick()
    assert r.sid == info.sid and r.t == 0 and r.k == 2
    assert r.z.shape == (CFG.d_embed,)
    mid = gw.session(info.sid)
    assert mid.frames == 1 and mid.last_k == 2 and mid.fill_fraction > 0
    final = gw.close_session(info.sid)
    assert final.frames == 1
    with pytest.raises(KeyError):
        gw.submit(info.sid, FrameRequest(t=1, mel=_mel(rng)))
    with pytest.raises(KeyError):
        gw.session(info.sid)
    # the row is reusable and starts clean
    info2 = gw.open_session()
    assert gw.session(info2.sid).fill_fraction == 0.0
    s = gw.stats()
    assert s.sessions_opened == 2 and s.sessions_closed == 1
    assert s.sessions_open == 1


def test_submit_rejects_batched_mel(params):
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 1),
                            capacity=2, qos_reserve=0)
    sid = gw.open_session().sid
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        gw.submit(sid, FrameRequest(t=0, mel=_mel(rng)[None]))


def test_admission_error_is_typed_fleet_full(params):
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 0),
                            capacity=2, qos_reserve=0)
    gw.open_session()
    gw.open_session()
    with pytest.raises(AdmissionError) as ei:
        gw.open_session()
    # the api error IS a FleetFullError (existing guards keep working)
    assert isinstance(ei.value, FleetFullError)
    assert ei.value.n_active == 2 and ei.value.capacity == 2
    assert gw.stats().admission_refusals == 1


def test_qos_classes_reserve_headroom(params):
    """BULK runs out first, then STANDARD; INTERACTIVE fills the fleet."""
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 0),
                            capacity=8, qos_reserve=2)
    for _ in range(4):
        gw.open_session(qos=QoSClass.BULK)      # admitted while free >= 5
    with pytest.raises(AdmissionError):
        gw.open_session(qos=QoSClass.BULK)      # free=4 < 1+2*2
    for _ in range(2):
        gw.open_session(qos=QoSClass.STANDARD)  # admitted while free >= 3
    with pytest.raises(AdmissionError):
        gw.open_session(qos=QoSClass.STANDARD)  # free=2 < 1+2
    for _ in range(2):
        gw.open_session(qos=QoSClass.INTERACTIVE)
    with pytest.raises(AdmissionError):
        gw.open_session(qos=QoSClass.INTERACTIVE)  # truly full
    assert gw.stats().sessions_open == 8


# ---------------------------------------------------------------------------
# k-bucket dispatch parity: gateway z bit-matches per-frame SplitEngine.run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize", [True, False])
def test_bucketed_dispatch_bit_matches_per_frame_run(params, quantize):
    n = 2 * (L + 1)   # every k twice -> every bucket is a real batch
    gw = StreamSplitGateway(CFG, params, policy=SpreadPolicy(L),
                            capacity=n, window=8, qos_reserve=0,
                            quantize_wire=quantize)
    eng = SplitEngine(CFG, quantize_wire=quantize)
    rng = np.random.default_rng(1)
    sids = [gw.open_session().sid for _ in range(n)]
    mels = {}
    for t in range(2):
        for sid in sids:
            mels[(sid, t)] = _mel(rng)
            gw.submit(sid, FrameRequest(t=t, mel=mels[(sid, t)]))
        results = gw.tick()
        assert len(results) == n
        assert sorted({r.k for r in results}) == list(range(L + 1))
        for r in results:
            z_ref, _ = eng.run(params, mels[(r.sid, r.t)][None], r.k)
            np.testing.assert_array_equal(
                r.z, np.asarray(z_ref)[0],
                err_msg=f"k={r.k} not bit-identical to per-frame run")


def test_results_in_submission_order_with_bucket_sizes(params):
    gw = StreamSplitGateway(CFG, params,
                            policy=EntropyThresholdPolicy(L, threshold=0.5,
                                                          offload_k=2),
                            capacity=6, window=8, qos_reserve=0)
    rng = np.random.default_rng(2)
    sids = [gw.open_session().sid for _ in range(6)]
    us = [0.1, 0.9, 0.2, 0.8, 0.3, 0.9]
    for i, sid in enumerate(sids):
        gw.submit(sid, FrameRequest(t=0, mel=_mel(rng), u=us[i]))
    results = gw.tick()
    assert [r.sid for r in results] == sids       # submission order
    for r, u in zip(results, us):
        assert r.k == (2 if u > 0.5 else L)
        assert r.route == ("split" if u > 0.5 else "edge")
        assert r.bucket_size == 3
    assert gw.stats().dispatches == 2             # two buckets, two dispatches


# ---------------------------------------------------------------------------
# Overlapped tick data plane: single-sync contract + PR-3 path parity
# ---------------------------------------------------------------------------

def test_async_tick_bit_matches_pr3_sync_path(params):
    """A mixed-k tick through the overlapped plane (staged H2D, async
    bucket chains, fused Pallas wire kernel, ONE sync) is bit-identical
    to the PR-3 per-bucket-sync dispatch — and performs exactly one
    device sync and one D2H embedding copy where PR-3 paid one round-trip
    per bucket (counted through the instrumented _block/_d2h hooks)."""
    n = 2 * (L + 1)   # every k twice -> L+1 buckets per tick (mixed-k)

    def mk(overlap):
        return StreamSplitGateway(CFG, params, policy=SpreadPolicy(L),
                                  capacity=n, window=8, qos_reserve=0,
                                  overlap=overlap)

    gw_a, gw_s = mk(True), mk(False)
    rng = np.random.default_rng(11)
    sids_a = [gw_a.open_session().sid for _ in range(n)]
    sids_s = [gw_s.open_session().sid for _ in range(n)]
    for t in range(2):
        mels = [_mel(rng) for _ in range(n)]
        for gw, sids in ((gw_a, sids_a), (gw_s, sids_s)):
            for i, sid in enumerate(sids):
                gw.submit(sid, FrameRequest(t=t, mel=mels[i]))
        for ra, rs in zip(gw_a.tick(), gw_s.tick()):
            np.testing.assert_array_equal(
                ra.z, rs.z, err_msg=f"k={ra.k} diverged from the sync path")
            assert ra.k == rs.k and ra.wire_bytes == rs.wire_bytes
            assert ra.bucket_size == rs.bucket_size
    sa, ss = gw_a.stats(), gw_s.stats()
    # THE contract: one sync + one D2H per tick, however many buckets
    assert sa.device_syncs_per_tick == 1
    assert sa.d2h_copies_per_tick == 1
    assert ss.device_syncs_per_tick == L + 1      # PR-3: one per bucket
    assert ss.d2h_copies_per_tick == L + 1
    # the whole tick's frames staged as ONE h2d transfer (pow2-padded so
    # arbitrary streaming tick sizes don't grow the gather compile
    # cache), measured
    from repro.core.fleet import pad_pow2
    assert sa.staged_h2d_bytes == \
        2 * pad_pow2(n) * CFG.frames * CFG.n_mels * 4
    assert ss.staged_h2d_bytes == 0               # PR-3 staged per bucket
    assert sa.frames == ss.frames == 2 * n


def test_pipelined_phase_ticks_bit_match_sequential(params):
    """``tick_launch``/``tick_collect`` interleaved across ticks (tick
    t+1 launched while tick t's chains are in flight — the serving
    runtime's cross-tick pipeline) serve bit-identical embeddings to the
    plain ``tick()`` loop, and every collected tick still reports
    exactly one device sync and one D2H copy."""
    n = L + 1
    def mk():
        return StreamSplitGateway(CFG, params, policy=SpreadPolicy(L),
                                  capacity=n, window=8, qos_reserve=0)

    gw_p, gw_s = mk(), mk()
    sids_p = [gw_p.open_session().sid for _ in range(n)]
    sids_s = [gw_s.open_session().sid for _ in range(n)]
    rng = np.random.default_rng(13)
    mels = [[_mel(rng) for _ in range(n)] for _ in range(3)]

    def submit(gw, sids, t):
        for i, sid in enumerate(sids):
            gw.submit(sid, FrameRequest(t=t, mel=mels[t][i]))

    # pipelined: two plans in flight before the first collect
    submit(gw_p, sids_p, 0)
    plan0 = gw_p.tick_launch()
    submit(gw_p, sids_p, 1)
    plan1 = gw_p.tick_launch()
    res_p = [gw_p.tick_collect(plan0), gw_p.tick_collect(plan1)]
    assert gw_p.stats().device_syncs_per_tick == 1
    assert gw_p.stats().d2h_copies_per_tick == 1
    submit(gw_p, sids_p, 2)
    res_p.append(gw_p.tick_collect(gw_p.tick_launch()))
    # sequential reference
    res_s = []
    for t in range(3):
        submit(gw_s, sids_s, t)
        res_s.append(gw_s.tick())
    for tick_p, tick_s in zip(res_p, res_s):
        for rp, rs in zip(tick_p, tick_s):
            np.testing.assert_array_equal(rp.z, rs.z)
            assert rp.k == rs.k and rp.t == rs.t
    sp, ss = gw_p.stats(), gw_s.stats()
    assert sp.ticks == ss.ticks == 3 and sp.frames == ss.frames == 3 * n
    assert sp.device_syncs_per_tick == 1 and sp.d2h_copies_per_tick == 1
    # the fleet rings saw the same launch-order ingest
    for a, b in zip(gw_p.backend.snapshot(), gw_s.backend.snapshot()):
        np.testing.assert_array_equal(a, b)


def test_tick_launch_requires_overlapped_plane(params):
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 1),
                            capacity=2, qos_reserve=0, overlap=False)
    with pytest.raises(RuntimeError):
        gw.tick_launch()


def test_refine_due_next_tick_predicts_refine(params):
    head_init, head_apply = _head()
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 2),
                            capacity=2, window=8, qos_reserve=0,
                            head_init=head_init, head_apply=head_apply,
                            refine_every=2)
    rng = np.random.default_rng(14)
    sid = gw.open_session().sid
    for t in range(4):
        due = gw.refine_due_next_tick()
        assert due == (t % 2 == 1)
        gw.submit(sid, FrameRequest(t=t, mel=_mel(rng), label=0))
        before = gw.stats().refine_rounds
        gw.tick()
        assert gw.stats().refine_rounds == before + (1 if due else 0)


def test_profile_tick_restores_per_bucket_timing(params):
    """``tick(profile=True)`` is the diagnostic mode: one sync per bucket
    (so per-bucket latency is measurable) while results stay identical."""
    n = L + 1
    ticks = iter(range(10_000))
    gw = StreamSplitGateway(CFG, params, policy=SpreadPolicy(L),
                            capacity=n, window=8, qos_reserve=0,
                            clock=lambda: 0.5 * next(ticks))
    rng = np.random.default_rng(12)
    sids = [gw.open_session().sid for _ in range(n)]
    for sid in sids:
        gw.submit(sid, FrameRequest(t=0, mel=_mel(rng)))
    results = gw.tick(profile=True)
    s = gw.stats()
    # one per bucket + the final reassembly-gather wait
    assert s.device_syncs_per_tick == n + 1
    assert s.d2h_copies_per_tick == 1             # embeddings still 1 copy
    # fake clock: each bucket spans one 0.5 s read pair -> 500 ms/frame
    assert all(r.latency_ms == 500.0 for r in results)


# ---------------------------------------------------------------------------
# Wire accounting through the gateway
# ---------------------------------------------------------------------------

def test_gateway_wire_bytes_match_boundary_bytes_every_k(params):
    n = L + 1
    gw = StreamSplitGateway(CFG, params, policy=SpreadPolicy(L),
                            capacity=n, window=8, qos_reserve=0)
    per_sample = boundary_bytes(CFG, dtype_bytes=1)
    rng = np.random.default_rng(3)
    sids = [gw.open_session().sid for _ in range(n)]
    for sid in sids:
        gw.submit(sid, FrameRequest(t=0, mel=_mel(rng)))
    for r in gw.tick():
        if r.k == L:
            assert r.wire_bytes == 0 and r.route == "edge"
        else:
            # +8: per-tensor scale/zero header of the INT8 wire format
            assert r.wire_bytes == per_sample[r.k] + 8, f"k={r.k}"
    info = gw.session(sids[0])
    assert info.wire_bytes == per_sample[0] + 8   # frame 0 ran k=0
    assert gw.stats().wire_bytes == sum(
        per_sample[k] + 8 for k in range(L))      # k=L ships nothing


# ---------------------------------------------------------------------------
# Refine cadence + lazy sync surface
# ---------------------------------------------------------------------------

def test_refine_cadence_and_sync_accounting(params):
    head_init, head_apply = _head()
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 2),
                            capacity=2, window=8, qos_reserve=0,
                            head_init=head_init, head_apply=head_apply,
                            refine_every=2)
    rng = np.random.default_rng(4)
    sid = gw.open_session().sid
    for t in range(4):
        gw.submit(sid, FrameRequest(t=t, mel=_mel(rng), label=t % N_CLASSES,
                                    bandwidth_mbps=30.0, charging=True))
        gw.tick()
    s = gw.stats()
    assert s.refine_rounds == 2                   # ticks 2 and 4
    assert np.isfinite(s.last_refine_loss)
    # lazy sync fired (weights push: charging + high bandwidth)
    assert s.sync_events >= 1 and s.sync_bytes > 0
    assert gw.session(sid).sync_bytes == s.sync_bytes


def test_atomic_transition_counting(params):
    gw = StreamSplitGateway(CFG, params,
                            policy=EntropyThresholdPolicy(L, threshold=0.5,
                                                          offload_k=1),
                            capacity=2, window=8, qos_reserve=0)
    rng = np.random.default_rng(5)
    sid = gw.open_session().sid
    for t, u in enumerate([0.1, 0.9, 0.9, 0.1]):  # L, 1, 1, L
        gw.submit(sid, FrameRequest(t=t, mel=_mel(rng), u=u))
        gw.tick()
    assert gw.session(sid).transitions == 2       # L->1 and 1->L


# ---------------------------------------------------------------------------
# Policy unification
# ---------------------------------------------------------------------------

def test_make_policy_covers_all_controller_kinds():
    obs = np.array([[0.1, 0.2, 0.9],    # low U, idle cpu, high bw
                    [0.9, 0.9, 0.01]],  # high U, busy cpu, dead link
                   np.float32)
    for kind, expected in [("edge", [L, L]), ("server", [0, 0]),
                           ("static", [3, 3]), ("rule", [2, L]),
                           ("entropy", [L, 2])]:
        pol = make_policy(kind, L)
        assert isinstance(pol, SplitPolicy)
        np.testing.assert_array_equal(pol.decide(obs), expected, err_msg=kind)
    with pytest.raises(ValueError):
        make_policy("nope", L)
    with pytest.raises(ValueError):
        make_policy("rl", L)                      # rl needs params


def test_rl_policy_batched_matches_greedy_action():
    from repro.core.ppo import greedy_action, init_policy
    rl_params = init_policy(jax.random.PRNGKey(0), 3, L + 1)
    pol = make_policy("rl", L, rl_params=rl_params)
    rng = np.random.default_rng(6)
    obs = rng.random((5, 3)).astype(np.float32)
    ks = pol.decide(obs)
    for i in range(5):
        assert ks[i] == greedy_action(rl_params, obs[i]), f"row {i}"


def test_gateway_rejects_mismatched_policy_action_space(params):
    with pytest.raises(ValueError):
        StreamSplitGateway(CFG, params, policy=FixedKPolicy(L + 3, 1))


# ---------------------------------------------------------------------------
# Fleet backend seam + injected clock
# ---------------------------------------------------------------------------

def test_injected_clock_makes_timing_deterministic(params):
    """Every timing stat derives from the injected clock: with a fake
    counter clock the latency/uptime numbers are exact, not wall-clock."""
    ticks = iter(range(10_000))
    gw = StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 2),
                            capacity=2, window=8, qos_reserve=0,
                            clock=lambda: 0.5 * next(ticks))
    sid = gw.open_session().sid
    rng = np.random.default_rng(7)
    gw.submit(sid, FrameRequest(t=0, mel=_mel(rng)))
    (r,) = gw.tick()
    # clock reads: ctor(0), the open_session phase(1, 2), the launch
    # phase(3, 14) around tick entry(4), the decide phase(5, 6), dispatch
    # start(7), the stage_h2d/dispatch/account phases(8..13), the
    # collect phase(15, 20) around the sync phase(16, 17), the latency
    # stamp(18) and tick exit(19), stats(21).
    # dispatch start(7) to the latency stamp(18): 5.5 s for one frame
    assert r.latency_ms == 5500.0
    s = gw.stats()
    # tick entry(4) .. exit(19): 7.5 s
    assert s.last_tick_ms == 7500.0
    assert s.uptime_s == 0.5 * 21
    # every phase span is on the same clock: launch 11 reads, decide 1
    assert s.phases["gateway.launch"] == (1, 5.5, 1)
    assert s.phases["gateway.decide"] == (1, 0.5, 1)


def test_gateway_on_sharded_backend_bit_matches_host(params):
    """The backend seam must not change serving results: a gateway over a
    1-shard device-resident backend serves bit-identical embeddings and
    refine losses, with zero snapshot h2d traffic."""
    from repro.api import ShardedFleetBackend
    head_init, head_apply = _head()

    def mk(backend=None):
        kw = dict(capacity=4, window=8, qos_reserve=0)
        if backend is None:
            kw.update(head_init=head_init, head_apply=head_apply)
        return StreamSplitGateway(CFG, params, policy=SpreadPolicy(L),
                                  refine_every=2, backend=backend, **kw)

    gw_h = mk()
    gw_s = mk(ShardedFleetBackend(
        capacity=4, window=8, dim=CFG.d_embed, head_init=head_init,
        head_apply=head_apply, lr=1e-2, seed=0))
    rng = np.random.default_rng(8)
    sids_h = [gw_h.open_session().sid for _ in range(4)]
    sids_s = [gw_s.open_session().sid for _ in range(4)]
    for t in range(4):
        mels = [_mel(rng) for _ in range(4)]
        for gw, sids in ((gw_h, sids_h), (gw_s, sids_s)):
            for i, sid in enumerate(sids):
                gw.submit(sid, FrameRequest(t=t, mel=mels[i],
                                            label=t % N_CLASSES))
        for rh, rs in zip(gw_h.tick(), gw_s.tick()):
            np.testing.assert_array_equal(rh.z, rs.z)
            assert rh.k == rs.k and rh.wire_bytes == rs.wire_bytes
    sh, ss = gw_h.stats(), gw_s.stats()
    assert sh.refine_rounds == ss.refine_rounds == 2
    assert ss.last_refine_loss == sh.last_refine_loss  # bitwise
    assert (sh.backend, sh.shards) == ("host", 1)
    assert (ss.backend, ss.shards) == ("sharded", 1)
    assert sum(ss.shard_frames) == ss.frames == 16
    assert ss.snapshot_h2d_bytes == 0 and sh.snapshot_h2d_bytes > 0
    # gateway hands embeddings to the sharded fleet as device arrays:
    # zero h2d payload, the full volume measured as device-to-device
    assert ss.ingest_h2d_bytes == 0
    assert gw_s.backend.ingest_d2d_bytes == ss.frames * CFG.d_embed * 4
    # session-level accounting rides the same seam
    assert gw_h.session(sids_h[0]).fill_fraction == \
        gw_s.session(sids_s[0]).fill_fraction


def test_gateway_rejects_backend_dim_mismatch(params):
    from repro.api import HostFleetBackend
    with pytest.raises(ValueError):
        StreamSplitGateway(CFG, params, policy=FixedKPolicy(L, 1),
                           backend=HostFleetBackend(
                               capacity=2, window=8, dim=CFG.d_embed + 1))


# ---------------------------------------------------------------------------
# Sharded dispatch plane (shard_dispatch=True; docs/SHARDING.md)
# ---------------------------------------------------------------------------

def test_shard_dispatch_one_shard_bitwise_matches_overlapped(params):
    """Forcing ``shard_dispatch`` on a 1-shard backend is the in-process
    bitwise-parity configuration of the sharded plane: identical
    results, identical staged bytes (the S=1 blocked layout IS the flat
    layout), the one-sync contract, and rings identical to the plain
    overlapped plane's ``insert_batch`` path."""
    from repro.api import ShardedFleetBackend

    def mk(**kw):
        return StreamSplitGateway(
            CFG, params, policy=SpreadPolicy(L), qos_reserve=0,
            backend=ShardedFleetBackend(capacity=6, window=8,
                                        dim=CFG.d_embed), **kw)

    gw_a = mk()                       # plain overlapped plane (auto-off)
    gw_b = mk(shard_dispatch=True)    # sharded plane on ONE shard
    assert not gw_a.shard_dispatch and gw_b.shard_dispatch
    rng = np.random.default_rng(3)
    sa = [gw_a.open_session().sid for _ in range(5)]
    sb = [gw_b.open_session().sid for _ in range(5)]
    for t in range(3):
        mels = [_mel(rng) for _ in range(5)]
        for gw, sids in ((gw_a, sa), (gw_b, sb)):
            for i, sid in enumerate(sids):
                gw.submit(sid, FrameRequest(t=t, mel=mels[i],
                                            label=t % N_CLASSES))
        for ra, rb in zip(gw_a.tick(), gw_b.tick()):
            np.testing.assert_array_equal(ra.z, rb.z)
            assert ra.k == rb.k and ra.wire_bytes == rb.wire_bytes
    st_a, st_b = gw_a.stats(), gw_b.stats()
    assert st_b.device_syncs_per_tick == 1
    assert st_b.d2h_copies_per_tick == 1
    assert st_b.staged_h2d_bytes == st_a.staged_h2d_bytes
    assert (st_a.dispatch_shards, st_b.dispatch_shards) == (1, 1)
    assert sum(st_b.dispatch_shard_frames) == st_b.frames == 15
    assert st_b.ingest_h2d_bytes == 0
    for xa, xb in zip(gw_a.backend.snapshot(), gw_b.backend.snapshot()):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_shard_dispatch_argument_validation(params):
    from repro.api import HostFleetBackend, ShardedFleetBackend
    with pytest.raises(ValueError, match="overlap"):
        StreamSplitGateway(
            CFG, params, policy=FixedKPolicy(L, 1), overlap=False,
            shard_dispatch=True,
            backend=ShardedFleetBackend(capacity=2, window=8,
                                        dim=CFG.d_embed))
    with pytest.raises(ValueError, match="device-resident"):
        StreamSplitGateway(
            CFG, params, policy=FixedKPolicy(L, 1), shard_dispatch=True,
            backend=HostFleetBackend(capacity=2, window=8,
                                     dim=CFG.d_embed))


def test_shard_dispatch_profile_reports_per_shard(params):
    """``tick(profile=True)`` surfaces per-shard stage timings next to
    the per-bucket split (``gateway.last_profile``) on BOTH planes —
    the single-device plane reports everything under shard 0."""
    from repro.api import ShardedFleetBackend
    rng = np.random.default_rng(5)

    def run_profiled(gw):
        sids = [gw.open_session().sid for _ in range(4)]
        assert gw.last_profile is None
        for sid in sids:
            gw.submit(sid, FrameRequest(t=0, mel=_mel(rng)))
        gw.tick(profile=True)
        return gw.last_profile

    prof = run_profiled(StreamSplitGateway(
        CFG, params, policy=SpreadPolicy(L), qos_reserve=0,
        shard_dispatch=True,
        backend=ShardedFleetBackend(capacity=4, window=8,
                                    dim=CFG.d_embed)))
    assert set(prof["per_shard"]) == {0}
    ps = prof["per_shard"][0]
    assert ps["frames"] == 4
    assert ps["chains"] == len(prof["per_bucket_ms"]) == 4
    assert set(ps["per_bucket_ms"]) == set(prof["per_bucket_ms"])
    assert all(v >= 0.0 for v in prof["per_bucket_ms"].values())
    # plain overlapped plane: same shape, shard 0 only
    prof_h = run_profiled(StreamSplitGateway(
        CFG, params, policy=SpreadPolicy(L), capacity=4, window=8,
        qos_reserve=0))
    assert set(prof_h["per_shard"]) == {0}
    assert prof_h["per_shard"][0]["frames"] == 4


_SHARDED_DISPATCH_PARITY = """
import jax, numpy as np
S = @S@
assert len(jax.devices()) == S
from repro.api import FrameRequest, ShardedFleetBackend, StreamSplitGateway
from repro.launch.mesh import make_sessions_mesh
from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder

CFG = AudioEncCfg(widths=(8, 8, 8, 8), strides=(1, 1, 1, 1), n_mels=8,
                  frames=8, d_embed=16, groups=2)
L = CFG.n_blocks
params = init_audio_encoder(CFG, jax.random.PRNGKey(0))

class Spread:
    def __init__(self, L):
        self.L = L
    def decide(self, obs):
        return np.arange(len(obs), dtype=np.int64) % (self.L + 1)

def mk(backend=None):
    return StreamSplitGateway(CFG, params, policy=Spread(L), capacity=8,
                              window=8, qos_reserve=0, backend=backend)

n = 7                      # != 0 mod S: uneven per-shard blocks
gw_ref = mk()              # host backend, single-device overlapped plane
gw_sh = mk(ShardedFleetBackend(capacity=8, window=8, dim=CFG.d_embed,
                               mesh=make_sessions_mesh(S)))
assert gw_sh.shard_dispatch, "shard_dispatch must auto-enable on shards>1"
rng = np.random.default_rng(0)
sr = [gw_ref.open_session().sid for _ in range(n)]
ss = [gw_sh.open_session().sid for _ in range(n)]

def feed(gw, sids, t, mels):
    for i, sid in enumerate(sids):
        gw.submit(sid, FrameRequest(t=t, mel=mels[i], label=t % 3))

# tick(): per-device chains, embeddings == the unsharded overlapped
# plane serving the same admitted order, bit for bit
for t in range(3):
    mels = [rng.normal(size=(8, 8)).astype(np.float32) for _ in range(n)]
    feed(gw_ref, sr, t, mels); feed(gw_sh, ss, t, mels)
    for rr, rs in zip(gw_ref.tick(), gw_sh.tick()):
        np.testing.assert_array_equal(rr.z, rs.z)
        assert rr.k == rs.k and rr.wire_bytes == rs.wire_bytes
st = gw_sh.stats()
assert st.device_syncs_per_tick == 1 and st.d2h_copies_per_tick == 1
assert st.dispatch_shards == S
assert sum(st.dispatch_shard_frames) == st.frames == 3 * n
assert all(f > 0 for f in st.dispatch_shard_frames)
assert st.ingest_h2d_bytes == 0   # scatter stayed shard-local

# a tick that leaves S-1 shards idle holds every contract too
m = rng.normal(size=(8, 8)).astype(np.float32)
gw_ref.submit(sr[0], FrameRequest(t=3, mel=m, label=0))
gw_sh.submit(ss[0], FrameRequest(t=3, mel=m, label=0))
np.testing.assert_array_equal(gw_ref.tick()[0].z, gw_sh.tick()[0].z)
assert gw_sh.stats().device_syncs_per_tick == 1

# interleaved tick_launch/tick_collect: the streaming runtime's
# pipelining seam — one sync per collected tick survives two plans in
# flight
mels1 = [rng.normal(size=(8, 8)).astype(np.float32) for _ in range(n)]
mels2 = [rng.normal(size=(8, 8)).astype(np.float32) for _ in range(n)]
feed(gw_sh, ss, 4, mels1)
p0 = gw_sh.tick_launch()
feed(gw_sh, ss, 5, mels2)
p1 = gw_sh.tick_launch()
r0 = gw_sh.tick_collect(p0)
r1 = gw_sh.tick_collect(p1)
assert gw_sh.stats().device_syncs_per_tick == 1
assert gw_sh.stats().d2h_copies_per_tick == 1
for t, mels, res in ((4, mels1, r0), (5, mels2, r1)):
    feed(gw_ref, sr, t, mels)
    for rr, rs in zip(gw_ref.tick(), res):
        np.testing.assert_array_equal(rr.z, rs.z)

# the placed scatter left the rings exactly as host-backend ingest did
# (admission order, not row index, is the cross-backend identity)
zh, mh, lh = (np.asarray(a) for a in gw_ref.backend.snapshot())
zd, md, ld = (np.asarray(a) for a in gw_sh.backend.snapshot())
np.testing.assert_array_equal(zh[np.array(sr)], zd[np.array(ss)])
np.testing.assert_array_equal(mh[np.array(sr)], md[np.array(ss)])
np.testing.assert_array_equal(lh[np.array(sr)], ld[np.array(ss)])

# misplacing a frame on a foreign shard's row block must raise, not
# silently scatter cross-shard
import jax.numpy as jnp
be = gw_sh.backend
zbad = jax.device_put(jnp.zeros((S, CFG.d_embed), jnp.float32),
                      be._sharding)
try:
    wrong = (int(be.shards_of(np.array([ss[0]]))[0]) + 1) % S
    be.insert_batch_placed(np.array([ss[0]]), np.array([99]), zbad, None,
                           np.array([wrong]))
    raise SystemExit("misplaced row was accepted")
except ValueError:
    pass

# per-shard profile: every shard reports its own stage timings
feed(gw_sh, ss, 6, mels1)
gw_sh.tick(profile=True)
prof = gw_sh.last_profile
assert set(prof["per_shard"]) == set(range(S))
assert sum(d["frames"] for d in prof["per_shard"].values()) == n
print("OK", S)
"""


@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_dispatch_multi_device_parity(subproc, shards):
    """Tentpole contract, end to end on forced host devices: per-device
    edge→wire→server chains over the sessions axis produce embeddings
    bit-identical to the unsharded overlapped plane, with ONE device
    sync and ONE D2H per collected tick — via ``tick()`` AND through
    the interleaved launch/collect pipelining seam — shard-local ring
    ingest, and per-shard profile timings."""
    out = subproc(_SHARDED_DISPATCH_PARITY.replace("@S@", str(shards)),
                  devices=shards)
    assert out.strip().endswith(f"OK {shards}")
