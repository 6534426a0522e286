"""Multi-gateway federation benchmark: a ``GatewayCluster`` of N member
``StreamServer``s under steady mixed-k load, with a live ``drain()``
(rolling-restart migration) in the middle of the run, plus a CHAOS lane
(seeded member kill mid-stream, replication off vs on — the loss bound
and the bit-identical journal-replay recovery are hard asserts).

**Lane — drain under load, N ∈ {2, 4} members.**  ``sessions_per_member``
sessions per member (consistent-hash placement), every session holding a
CONSTANT uncertainty so its k-bucket is stable tick-to-tick.  Because
the fleet executables are jitted per gateway *instance*, a receiver that
has never served a migrated composition pays XLA compile on first
contact — so the lane warms with a full dry drain → ``add_member``
rejoin cycle (which itself exercises the rebalance path both ways), then
times three phases:

- ``before``        — steady state, all members serving;
- ``during_drain``  — the same offered load with a ``drain(victim)``
  dropped mid-round, so the victim's sessions quiesce, export and
  import onto ring-chosen survivors (books + token bucket + queued
  frames with original deadlines) while traffic keeps flowing;
- ``after``         — steady state on the survivors.

Reported (and written to ``BENCH_cluster.json``): frames/s per phase,
warm migration pause p50/p95/max ms (wall-clock per session move:
quiesce → export → import), the cold first-contact pause for contrast,
and migrated frame/byte volume.

Hard asserts — a failure fails the process loudly (CI smoke runs this):

- the cluster-wide per-class conservation identity ``submitted ==
  served + queue_depth + in_flight + shed_expired + lost_in_flight``
  holds at every sampled snapshot, and after the final pump every
  accepted frame was served (zero shed, zero lost — a drain drops
  nothing);
- exactly the victim's sessions migrated, and queued frames travelled
  with them (``migrated_frames > 0``);
- **bit-parity**: every migrated session's full served stream (z, k)
  is bit-identical to an unmigrated replay of the same frames on a
  fresh single gateway — migration is invisible to the embedding.

    PYTHONPATH=src python -m benchmarks.cluster_serve [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import row
from benchmarks.gateway_serve import DEEP_KW, MixedKPolicy

SESSIONS_PER_MEMBER = 4
WARMUP_ROUNDS = 2


def _mel(gsid, t, cfg):
    rng = np.random.default_rng(1000 * (gsid + 1) + t)
    return rng.normal(size=(cfg.frames, cfg.n_mels)).astype(np.float32)


def _req(gsid, t, cfg, us):
    from repro.api import FrameRequest
    return FrameRequest(t=t, mel=_mel(gsid, t, cfg), u=us[gsid])


def _member(cfg, params, n):
    from repro.api import StreamSplitGateway
    from repro.serving import SchedulerCfg, StreamServer
    gw = StreamSplitGateway(cfg, params, policy=MixedKPolicy(cfg.n_blocks),
                            capacity=n, window=16, qos_reserve=0,
                            overlap=True)
    # constructed UNSTARTED: the cluster owns stepping
    return StreamServer(gw, cfg=SchedulerCfg(max_batch=n),
                        queue_maxlen=16 * n)


def _pcts(ms):
    if not ms:
        return {"p50": 0.0, "p95": 0.0, "max": 0.0}
    a = np.asarray(ms, np.float64)
    return {"p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)), "max": float(a.max())}


def bench_cluster_drain(members=2, *, rounds=8,
                        spm=SESSIONS_PER_MEMBER):
    """-> one lane result dict for an N-member cluster."""
    from repro.api import StreamSplitGateway
    from repro.cluster import GatewayCluster
    from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder
    cfg = AudioEncCfg(**DEEP_KW)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))
    n = members * spm
    # constant per-session uncertainty spread over every k-bucket: the
    # bucket composition is stable tick-to-tick, so compiles land in
    # the warmup cycle and the phase numbers measure serving, not XLA
    us = [float(u) for u in
          np.random.default_rng(3).permutation(np.linspace(0.02, 0.98, n))]

    results = []
    servers = {f"g{i}": _member(cfg, params, n) for i in range(members)}
    cl = GatewayCluster(dict(servers), seed=0, on_result=results.append)
    infos = [cl.open_session() for _ in range(n)]
    t_next = 0

    def round_(*, drain=None):
        nonlocal t_next
        for i in infos:
            cl.submit(i.sid, _req(i.sid, t_next, cfg, us))
        if drain is not None:     # mid-round: queued frames must travel
            cl.drain(drain)
        cl.step()
        t_next += 1

    def conserved():
        st = cl.stats()
        assert st.conserved, (st.submitted, st.served, st.queue_depth,
                              st.in_flight, st.shed_expired,
                              st.lost_in_flight)
        return st

    def timed(fn):
        s0 = sum(cl.stats().served.values())
        t0 = time.perf_counter()
        fn()
        cl.pump()
        dt = time.perf_counter() - t0
        conserved()
        return (sum(cl.stats().served.values()) - s0) / dt

    victim = sorted({cl.session_member(i.sid) for i in infos})[0]
    homed = [i.sid for i in infos if cl.session_member(i.sid) == victim]

    # warm cycle: per-member compositions, then a full drain so every
    # survivor compiles the migrated compositions (import + encode),
    # then the rejoin (rebalance moves ownership straight back)
    for _ in range(WARMUP_ROUNDS):
        round_()
    round_(drain=victim)
    for _ in range(WARMUP_ROUNDS):
        round_()
    assert cl.add_member(victim, servers[victim]) == len(homed)
    round_()
    cl.pump()
    st0 = conserved()
    assert st0.migrations == 2 * len(homed) > 0
    cold_pause = _pcts(cl.migration_pauses_ms)

    def steady():
        for _ in range(rounds):
            round_()

    fps_before = timed(steady)

    def drain_phase():
        for _ in range(rounds // 2):
            round_()
        round_(drain=victim)              # live: queued frames travel
        for _ in range(rounds - rounds // 2 - 1):
            round_()

    fps_during = timed(drain_phase)
    fps_after = timed(steady)

    st = conserved()
    assert st.drains - st0.drains == 1
    assert st.migrations - st0.migrations == len(homed)
    assert st.migrated_frames - st0.migrated_frames >= len(homed)
    assert victim not in st.members
    # drained to empty: every accepted frame served, nothing shed/lost
    assert st.served == st.submitted, (st.served, st.submitted)
    assert sum(st.shed_expired.values()) == 0
    assert sum(st.lost_in_flight.values()) == 0
    total = t_next * n
    assert len(results) == total and sum(st.served.values()) == total
    warm_pause = _pcts(cl.migration_pauses_ms[st0.migrations:])

    # bit-parity oracle: replay each MIGRATED session's frames on a
    # fresh never-clustered gateway — z and k must match bitwise
    by_sid = {}
    for r in results:
        by_sid.setdefault(r.sid, {})[r.t] = r
    oracle = StreamSplitGateway(cfg, params,
                                policy=MixedKPolicy(cfg.n_blocks),
                                capacity=len(homed), window=16,
                                qos_reserve=0, overlap=True)
    for gsid in homed:
        assert sorted(by_sid[gsid]) == list(range(t_next))
        osid = oracle.open_session().sid
        for t in range(t_next):
            oracle.submit(osid, _req(gsid, t, cfg, us))
            (ref,) = oracle.tick()
            got = by_sid[gsid][t]
            assert (got.z == ref.z).all() and got.k == ref.k, \
                f"migrated session {gsid} diverged at t={t}"

    for i in infos:
        cl.close_session(i.sid)
    st = conserved()
    assert st.sessions_open == 0
    return {
        "members": members,
        "sessions": n,
        "rounds_per_phase": rounds,
        "frames_per_s": {"before": fps_before,
                         "during_drain": fps_during,
                         "after": fps_after},
        "migration_pause_ms": warm_pause,
        "migration_pause_cold_ms": cold_pause,
        "migrations": st.migrations - st0.migrations,
        "migrated_frames": st.migrated_frames - st0.migrated_frames,
        "migrated_bytes": st.migrated_bytes - st0.migrated_bytes,
        "bit_identical_migrated": True,
        "shed_expired": sum(st.shed_expired.values()),
        "lost_in_flight": sum(st.lost_in_flight.values()),
    }


def _chaos_once(*, replicate, members, rounds, spm, cfg, params, us,
                seed=0):
    """One seeded kill-mid-stream run; same schedule, same kill step,
    replication on or off.  Returns (cluster, infos, results, kill_step,
    victim)."""
    from repro.cluster import FailureInjector, GatewayCluster, HashRing
    n = members * spm
    names = [f"g{i}" for i in range(members)]
    # the victim is the ring owner of gsid 0 — computable before the
    # cluster exists (the ring is a pure function of membership + seed),
    # so the injector can be installed at construction
    victim = HashRing(names, seed=seed).owner(0)
    kill_step = WARMUP_ROUNDS + max(1, rounds // 2)
    results = []
    cl = GatewayCluster({nm: _member(cfg, params, n) for nm in names},
                        seed=seed, snapshot_every=2, replicate=replicate,
                        on_result=results.append,
                        injectors={victim: FailureInjector(
                            fail_at=(kill_step,))})
    infos = [cl.open_session() for _ in range(n)]
    assert cl.session_member(infos[0].sid) == victim
    # every round_ below is exactly one cluster step — no intermediate
    # pump, so the injector's step id maps 1:1 onto the round index
    t_next = 0

    def round_():
        nonlocal t_next
        for i in infos:
            cl.submit(i.sid, _req(i.sid, t_next, cfg, us))
        cl.step()
        t_next += 1
        st = cl.stats()
        assert st.conserved, (st.submitted, st.served, st.queue_depth,
                              st.in_flight, st.shed_expired,
                              st.lost_in_flight)

    for _ in range(WARMUP_ROUNDS + rounds):
        round_()
    cl.pump()
    st = cl.stats()
    assert st.conserved and st.failures == 1
    assert victim not in st.members
    assert st.sessions_open == n and cl.lost_sessions == []
    return cl, infos, results, t_next, victim


def bench_cluster_chaos(members=2, *, rounds=8,
                        spm=SESSIONS_PER_MEMBER):
    """Seeded member kill mid-stream, replication OFF vs ON — the
    self-healing lane.  Hard asserts: the ON run loses STRICTLY fewer
    frames than the OFF run on the same schedule (with a per-step
    journal flush: zero), and every recovered stream's (z, k) is
    bit-identical to an unfailed replay on a fresh single gateway."""
    from repro.api import StreamSplitGateway
    from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder
    cfg = AudioEncCfg(**DEEP_KW)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))
    n = members * spm
    us = [float(u) for u in
          np.random.default_rng(3).permutation(np.linspace(0.02, 0.98, n))]

    cl_off, _, _, _, _ = _chaos_once(replicate=False, members=members,
                                     rounds=rounds, spm=spm, cfg=cfg,
                                     params=params, us=us)
    lost_off = sum(cl_off.stats().lost_in_flight.values())
    assert lost_off > 0     # checkpoint-only recovery drops the backlog

    t0 = time.perf_counter()
    cl_on, infos, results, t_next, victim = _chaos_once(
        replicate=True, members=members, rounds=rounds, spm=spm,
        cfg=cfg, params=params, us=us)
    dt = time.perf_counter() - t0
    st = cl_on.stats()
    lost_on = sum(st.lost_in_flight.values())
    assert lost_on < lost_off            # the headline loss bound
    assert lost_on == 0                  # per-step flush: zero loss
    assert st.failovers > 0 and st.replayed_frames > 0
    assert st.served == st.submitted
    assert sum(st.shed_expired.values()) == 0

    # replay-parity oracle over EVERY session (recovered and not):
    # checkpoint + journal replay must be invisible to the embedding
    by_sid = {}
    for r in results:
        assert r.t not in by_sid.setdefault(r.sid, {})   # no dupes
        by_sid[r.sid][r.t] = r
    oracle = StreamSplitGateway(cfg, params,
                                policy=MixedKPolicy(cfg.n_blocks),
                                capacity=n, window=16,
                                qos_reserve=0, overlap=True)
    for gsid in sorted(by_sid):
        assert sorted(by_sid[gsid]) == list(range(t_next))
        osid = oracle.open_session().sid
        for t in range(t_next):
            oracle.submit(osid, _req(gsid, t, cfg, us))
            (ref,) = oracle.tick()
            got = by_sid[gsid][t]
            assert (got.z == ref.z).all() and got.k == ref.k, \
                f"recovered session {gsid} diverged at t={t}"

    for i in infos:
        cl_on.close_session(i.sid)
    return {
        "members": members,
        "sessions": n,
        "rounds": rounds,
        "victim": victim,
        "frames_per_s": (t_next * n) / dt,
        "lost_replication_off": lost_off,
        "lost_replication_on": lost_on,
        "failovers": st.failovers,
        "replayed_frames": st.replayed_frames,
        "journal_bytes": st.journal_bytes,
        "retries": st.retries,
        "bit_identical_replay": True,
    }


def run_all(*, quick=False, smoke=False):
    result = {"cluster": {}}
    rounds = 4 if smoke else (6 if quick else 10)
    for m in (2, 4):
        r = bench_cluster_drain(m, rounds=rounds)
        result["cluster"][m] = r
        p = r["migration_pause_ms"]
        row(f"cluster.migration_pause.N{m}", p["p50"] * 1e3,
            f"ms*1e3 p50 warm; p95 {p['p95']:.2f}ms max {p['max']:.2f}ms "
            f"(cold max {r['migration_pause_cold_ms']['max']:.0f}ms), "
            f"{r['migrations']} sessions moved, "
            f"{r['migrated_frames']} queued frames, "
            f"{r['migrated_bytes']} B")
        fps = r["frames_per_s"]
        row(f"cluster.drain_fps.N{m}", 1e6 / max(fps["during_drain"], 1e-9),
            f"{fps['during_drain']:.0f} frames/s during drain "
            f"(before {fps['before']:.0f}, after {fps['after']:.0f}), "
            "0 shed, 0 lost, bit-identical migrated replay")
    c = bench_cluster_chaos(2, rounds=rounds)
    result["chaos"] = {2: c}
    row("cluster.chaos_lost_frames", float(c["lost_replication_on"]),
        f"lost with replication ON (OFF run: "
        f"{c['lost_replication_off']}), {c['failovers']} failovers, "
        f"{c['replayed_frames']} journal frames replayed "
        f"({c['journal_bytes']} B shipped), bit-identical recovery")
    print("BENCH " + json.dumps({"bench": "cluster_serve", **result}))
    return result


def write_bench_json(result, path="BENCH_cluster.json"):
    """Machine-readable federation trajectory (CI artifact — see
    docs/FEDERATION.md for the schema)."""
    doc = {"bench": "cluster_serve", "schema": 1,
           "backend": jax.default_backend(), **result}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config: fewest rounds that still "
                         "exercise every assert")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    out = run_all(quick=args.quick, smoke=args.smoke)
    print("wrote", write_bench_json(out))
