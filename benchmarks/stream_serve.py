"""Streaming serving benchmark: the always-on pipelined ``StreamServer``
vs the hand-rolled sequential ``submit``+``tick`` loop, plus a QoS
overload lane.

**Lane 1 — mixed-k throughput.**  N concurrent sessions, the deep thin
encoder of ``gateway_serve``'s mixed-k lane (L=8 -> 9 k-buckets per
tick), identical pre-built frames for every path:

- ``seq_sync``  — sequential loop over ``overlap=False`` (the PR-3
  per-bucket-sync dispatch: the fully *synchronous* serving model, one
  host staging + one blocking device round-trip per bucket);
- ``seq_async`` — sequential loop over the overlapped single-sync tick
  (PR 4's data plane, still one thread driving submit→tick→results);
- ``server``    — the threaded ``StreamServer``: clients submit from
  their own thread, the serving thread pipelines tick t+1's staging
  under tick t's in-flight chains.

Hard asserts: server embeddings **bit-identical** per (sid, t) to the
sequential gateway serving the same frames, and
``device_syncs_per_tick == 1`` throughout.  Speedups are *reported* (and
written to ``BENCH_stream.json``): the ≥1.3x target is against the
synchronous loop and, like every overlap number in this repo, is
regime-bound — on a 2-core CPU runner the "device" shares cores with
the host thread, so both overlap layers win only what the spare cores
can absorb (docs/PERF.md's regime note; on an accelerator backend every
blocking round-trip the baselines pay is a real stall).

**Lane 2 — synthetic overload.**  Offered load 2x tick capacity across
the three QoS classes with bounded queues (producer paced by
backpressure).  Hard asserts: conservation (accepted == served +
backlog + shed; ``preempted == requeued`` > 0 and only BULK),
INTERACTIVE p95 queue wait < BULK p50, INTERACTIVE misses no deadlines.
Reports per-class p50/p95 queue waits, deadline-miss rates and shed
counts.

**Lane 3 — sustained overload, deterministic.**  ~2x capacity for the
WHOLE run on a stepped fake clock: mixed tenants (weighted STANDARD,
a rate-limited chatty tenant, BULK beyond the aging quota).  Hard
asserts: no BULK starvation with the terminal wait bounded by
``deadline + shed_horizon + 2 ticks``, weighted DRR honors 2:1, real
sheds are visible in ``shed_expired``, and two independent runs are
bit-identical — a fairness regression fails loudly, never flakes.

    PYTHONPATH=src python -m benchmarks.stream_serve [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import row
from benchmarks.gateway_serve import DEEP_KW, MixedKPolicy

N = 32
WARMUP_ROUNDS = 2


def _build(n, rounds_total):
    from repro.api import FrameRequest
    from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder
    cfg = AudioEncCfg(**DEEP_KW)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    us = rng.permutation(np.linspace(0.02, 0.98, n))
    frames = [[FrameRequest(
        t=t, mel=rng.normal(size=(cfg.frames, cfg.n_mels)).astype(
            np.float32), u=float(us[i]))
        for i in range(n)] for t in range(rounds_total)]
    return cfg, params, frames


def _gateway(cfg, params, n, *, overlap=True):
    from repro.api import StreamSplitGateway
    return StreamSplitGateway(cfg, params, policy=MixedKPolicy(cfg.n_blocks),
                              capacity=n, window=16, qos_reserve=0,
                              overlap=overlap)


def bench_stream(n=N, *, rounds=24, repeats=3):
    """-> lane-1 result dict.  Interleaved best-of-repeats (machine
    drift hits every path equally); bit-parity asserted on the warmup
    rounds BEFORE anything is timed."""
    from repro.serving import QueueFullError, SchedulerCfg, StreamServer
    rounds_total = WARMUP_ROUNDS + rounds * repeats
    cfg, params, frames = _build(n, rounds_total)

    lanes = {
        "seq_sync": dict(gw=_gateway(cfg, params, n, overlap=False)),
        "seq_async": dict(gw=_gateway(cfg, params, n)),
    }
    for ln in lanes.values():
        ln["sids"] = [ln["gw"].open_session().sid for _ in range(n)]
        ln["best"] = float("inf")
        ln["results"] = {}
    # open-loop ingest: the queue bound exceeds one repeat's offered
    # load, so the producer never stalls inside a timed region (the
    # bounded-queue/backpressure regime is lane 2's subject)
    server_gw = _gateway(cfg, params, n)
    srv = StreamServer(server_gw, cfg=SchedulerCfg(max_batch=n),
                       queue_maxlen=(rounds + WARMUP_ROUNDS) * n)
    srv_sids = [srv.open_session().sid for _ in range(n)]
    srv_best = float("inf")

    def seq_round(ln, t):
        for i, sid in enumerate(ln["sids"]):
            ln["gw"].submit(sid, frames[t][i])
        for r in ln["gw"].tick():
            ln["results"][(r.sid, r.t)] = r

    def srv_pump(t):
        for i, sid in enumerate(srv_sids):
            while True:
                try:
                    srv.submit(sid, frames[t][i])
                    break
                except QueueFullError:     # bounded queue: backpressure
                    time.sleep(1e-4)

    srv_results = {}

    def srv_drain_into():
        for r in srv.drain_results():
            srv_results[(r.sid, r.t)] = r

    with srv:
        # warmup: compile every per-k executable + pow2 bucket shape on
        # every path, and pin bit-parity BEFORE the timed region
        for t in range(WARMUP_ROUNDS):
            for ln in lanes.values():
                seq_round(ln, t)
            srv_pump(t)
        while srv.served_total < WARMUP_ROUNDS * n:
            time.sleep(1e-3)
        srv_drain_into()
        for t in range(WARMUP_ROUNDS):
            for i in range(n):
                key = (srv_sids[i], t)
                za = srv_results[key].z
                for ln in lanes.values():
                    zs = ln["results"][(ln["sids"][i], t)].z
                    assert (za == zs).all(), \
                        f"server diverged from sequential at {key}"
        # timed: interleave the three paths per repeat
        t_base = WARMUP_ROUNDS
        for rep in range(repeats):
            for name, ln in lanes.items():
                t0 = time.perf_counter()
                for t in range(t_base, t_base + rounds):
                    seq_round(ln, t)
                ln["best"] = min(ln["best"], time.perf_counter() - t0)
            done = srv.served_total
            t0 = time.perf_counter()
            for t in range(t_base, t_base + rounds):
                srv_pump(t)
            while srv.served_total < done + rounds * n:
                time.sleep(1e-3)
            srv_best = min(srv_best, time.perf_counter() - t0)
            t_base += rounds
        srv_drain_into()
    st = srv.stats()

    # full-run bit-parity: every frame the server ever served, against
    # the sequential gateway that served the same frame
    assert len(srv_results) == rounds_total * n
    for (sid, t), r in srv_results.items():
        i = srv_sids.index(sid)
        ref = lanes["seq_sync"]["results"][(lanes["seq_sync"]["sids"][i], t)]
        assert (r.z == ref.z).all() and r.k == ref.k, \
            f"server diverged from sequential at {(sid, t)}"
    # the single-sync contract survived pipelining
    assert st.gateway.device_syncs_per_tick == 1
    assert st.gateway.d2h_copies_per_tick == 1
    assert st.pipelined_ticks > 0, "server never overlapped a tick"

    fps = {name: n * rounds / ln["best"] for name, ln in lanes.items()}
    fps["server"] = n * rounds / srv_best
    return {
        "n": n,
        "frames_per_s": fps,
        "speedup_vs_sync": fps["server"] / fps["seq_sync"],
        "speedup_vs_async": fps["server"] / fps["seq_async"],
        "pipelined_tick_fraction": st.pipelined_ticks / max(st.ticks, 1),
        "device_syncs_per_tick": st.gateway.device_syncs_per_tick,
        "bit_identical": True,
    }


def bench_overload(*, rounds=160, capacity=16, max_batch=8):
    """-> lane-2 result dict: 2x offered load, bounded queues, QoS
    isolation measured on the real clock.

    Traffic shape: a big BULK backlog lands first, then the
    latency-sensitive classes arrive in bursts — every INTERACTIVE /
    STANDARD frame that lands while the next (all-BULK) tick is staged
    under the in-flight chains preempts a staged BULK frame.  One
    k-bucket (fixed-k policy) keeps the lane's compile surface tiny;
    the QoS machinery is class-level, not k-level."""
    from repro.api import FrameRequest, QoSClass, StreamSplitGateway
    from repro.api.policies import FixedKPolicy
    from repro.serving import QueueFullError, SchedulerCfg, StreamServer
    from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder
    I, S, B = QoSClass.INTERACTIVE, QoSClass.STANDARD, QoSClass.BULK
    cfg = AudioEncCfg(**DEEP_KW)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))
    gw = StreamSplitGateway(cfg, params,
                            policy=FixedKPolicy(cfg.n_blocks, 4),
                            capacity=capacity, window=16, qos_reserve=0)
    deadline_ms = {I: 1000.0, S: 1000.0, B: 150.0}
    srv = StreamServer(gw, cfg=SchedulerCfg(max_batch=max_batch,
                                            deadline_ms=deadline_ms),
                       queue_maxlen=8 * capacity,
                       queue_maxlens={B: 1 << 16})
    sids = ([(srv.open_session(qos=I).sid, I) for _ in range(2)]
            + [(srv.open_session(qos=S).sid, S) for _ in range(2)]
            + [(srv.open_session(qos=B).sid, B)
               for _ in range(capacity - 4)])
    bulk_sids = [sid for sid, q in sids if q is B]
    fast_sids = [sid for sid, q in sids if q is not B]
    rng = np.random.default_rng(1)
    mels = [rng.normal(size=(cfg.frames, cfg.n_mels)).astype(np.float32)
            for _ in range(64)]
    accepted = 0
    tick_of = {}                           # rolling frame index per sid

    def bulk_burst(k):
        nonlocal accepted
        sent = 0
        for j in range(k):
            sid = bulk_sids[j % len(bulk_sids)]
            t = tick_of[sid] = tick_of.get(sid, -1) + 1
            try:
                srv.submit(sid, FrameRequest(t=t, mel=mels[t % 64]))
                accepted += 1
                sent += 1
            except QueueFullError:         # shed BULK: counted, reported
                pass
        return sent

    with srv:
        # warmup + service-rate probe (compile happens here, unpaced)
        bulk_burst(64)
        while srv.served_total < 64:
            time.sleep(1e-3)
        t0 = time.perf_counter()
        bulk_burst(256)
        while srv.served_total < 64 + 256:
            time.sleep(1e-3)
        rate = 256 / (time.perf_counter() - t0)   # frames/s, post-compile
        # phase 1: a BULK flood deep enough that draining it takes >> the
        # BULK deadline budget, whatever this machine's service rate is
        backlog = max(12 * rounds, int(4 * rate * deadline_ms[B] * 1e-3))
        t_serve0 = time.perf_counter()
        bulk_burst(backlog)
        # phase 2: latency-class bursts, self-paced one tick apart —
        # each burst lands while an all-BULK tick is staged under the
        # in-flight chains, exactly the preemption window
        for t in range(rounds):
            target = srv.served_total + max_batch
            while srv.served_total < target:
                time.sleep(1e-4)
            for sid in fast_sids:
                while True:
                    try:
                        srv.submit(sid, FrameRequest(
                            t=t, mel=mels[t % 64]))
                        accepted += 1
                        break
                    except QueueFullError:
                        time.sleep(1e-4)
        # phase 3: drain most of the backlog so late-admitted BULK
        # frames carry queue waits far beyond their deadline budget
        # (poll the bare queue depth — stats() rebuilds percentile
        # snapshots and would contend with the thread being measured)
        while srv.queues.depths()["bulk"] > backlog // 3:
            time.sleep(5e-3)
        srv.stop(drain=False)              # keep the rest measurable
    serve_s = time.perf_counter() - t_serve0
    st = srv.stats()

    # conservation: every accepted frame is served, still queued, or
    # (with a shed horizon configured — not in this lane) visibly shed
    assert sum(st.frames_submitted.values()) == accepted
    for c in st.frames_submitted:
        assert st.frames_submitted[c] == (st.frames_served[c]
                                          + st.queue_depth[c]
                                          + st.in_flight[c]
                                          + st.shed_expired[c]), c
    assert st.preempted == st.requeued
    assert st.preempted["bulk"] > 0, "2x overload must preempt BULK"
    assert st.preempted["interactive"] == st.preempted["standard"] == 0
    w = st.queue_wait_ms
    assert w["interactive"]["p95"] < w["bulk"]["p50"], \
        (w["interactive"], w["bulk"])
    # self-consistent, CI-robust form of "INTERACTIVE misses nothing":
    # a miss may only exist if some measured wait actually crossed the
    # budget (a runner stall, not a scheduling bug) — the zero-miss
    # absolute is pinned deterministically in tests/test_serving.py
    assert (st.deadline_misses["interactive"] == 0
            or w["interactive"]["max"] >= deadline_ms[I]), \
        (st.deadline_misses, w["interactive"])
    assert st.deadline_misses["bulk"] > 0, \
        "a backlog deeper than the BULK budget must miss deadlines"
    served = {c: max(v, 1) for c, v in st.frames_served.items()}
    return {
        "offered_per_round": len(sids),
        "max_batch": max_batch,
        "rounds": rounds,
        "accepted": accepted,
        "served": st.frames_served,
        "backlog": st.queue_depth,
        "rejected_full": st.rejected_full,
        "shed_expired": st.shed_expired,
        "preempted": st.preempted,
        "deadline_ms": {q.value: v for q, v in deadline_ms.items()},
        "deadline_miss_rate": {c: st.deadline_misses[c] / served[c]
                               for c in served},
        "queue_wait_ms": w,
        "frames_per_s": sum(st.frames_served.values()) / max(serve_s, 1e-9),
    }


def bench_sustained(*, rounds=240, max_batch=8):
    """-> lane-3 result dict: SUSTAINED overload (~2x capacity for the
    whole run, not a burst), mixed tenants, every scheduling decision on
    a stepped fake clock — the lane is bit-reproducible, so a fairness
    regression fails loudly instead of flaking.

    Tenants: one INTERACTIVE (3 frames/tick, tight deadline), three
    STANDARD — two equal-weight plus one double-weight — and a "chatty"
    STANDARD tenant offering 3x its token-bucket budget, and one BULK
    tenant offering more than the aging lane can promote (so real sheds
    happen deterministically).

    Hard asserts: no starvation (BULK keeps being served via aged
    promotion while STANDARD backlog never clears), BULK terminal wait
    bounded by ``deadline + shed_horizon + 2 ticks``, INTERACTIVE
    misses zero deadlines, DRR honors the 2:1 weight, the chatty tenant
    is capped at its token-bucket rate without hurting its peers, the
    extended conservation invariant holds at every sampled snapshot,
    and TWO independent runs produce identical schedules, sheds and
    counters."""
    from repro.api import FrameRequest, QoSClass, StreamSplitGateway
    from repro.api.policies import FixedKPolicy
    from repro.serving import (QueueFullError, RateLimitError,
                               SchedulerCfg, StreamServer)
    from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder
    I, S, B = QoSClass.INTERACTIVE, QoSClass.STANDARD, QoSClass.BULK
    cfg = AudioEncCfg(**DEEP_KW)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))
    DT = 0.05                              # one tick per 50 ms of fake time
    deadline_ms = {I: 200.0, S: 2000.0, B: 1000.0}
    shed_horizon_ms = 400.0
    max_wait_ms = {B: 600.0}

    class _FakeClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    def run_once():
        clock = _FakeClock()
        gw = StreamSplitGateway(cfg, params,
                                policy=FixedKPolicy(cfg.n_blocks, 4),
                                capacity=8, window=16, qos_reserve=0,
                                clock=clock)
        # queues sized so SERVING (not queue-full) is the bottleneck for
        # S/B: the shed horizon bounds the backlog instead — a full
        # shared class queue would ration acceptance by submit order
        # and mask the scheduler's fairness (lane 2 owns that regime)
        srv = StreamServer(gw, cfg=SchedulerCfg(
            max_batch=max_batch, deadline_ms=deadline_ms,
            max_wait_ms=max_wait_ms, promote_quota=0.25,
            shed_horizon_ms=shed_horizon_ms), queue_maxlen=64,
            queue_maxlens={S: 4096, B: 512})
        inter = srv.open_session(qos=I).sid
        # 40 tokens/s at DT=0.05 -> 2 accepted/tick; the tenant offers 6
        chatty = srv.open_session(qos=S, rate_limit=(40.0, 4)).sid
        std_w1 = srv.open_session(qos=S).sid
        std_w2 = srv.open_session(qos=S, weight=2.0).sid
        bulk = srv.open_session(qos=B).sid
        rng = np.random.default_rng(7)
        mels = [rng.normal(size=(cfg.frames, cfg.n_mels)).astype(np.float32)
                for _ in range(32)]
        served_by = {sid: 0 for sid in (inter, chatty, std_w1, std_w2,
                                        bulk)}
        accepted = 0
        tick_of = {}

        def offer(sid, k):
            nonlocal accepted
            for _ in range(k):
                t = tick_of[sid] = tick_of.get(sid, -1) + 1
                try:
                    srv.submit(sid, FrameRequest(t=t, mel=mels[t % 32]))
                    accepted += 1
                except (QueueFullError, RateLimitError):
                    pass                   # typed refusal: counted, visible

        def pump():
            srv.step()
            for res in srv.drain_results():
                served_by[res.sid] += 1
            clock.t += DT

        def check_conservation():
            st = srv.stats()
            for c in st.frames_submitted:
                assert st.frames_submitted[c] == (
                    st.frames_served[c] + st.queue_depth[c]
                    + st.in_flight[c] + st.shed_expired[c]), (c, st)
            assert st.preempted == st.requeued
            return st

        # stepped, not threaded: the serving thread only ever runs
        # step(), so this IS the serving loop — minus nondeterminism
        for r_ in range(rounds):
            offer(inter, 3)
            offer(chatty, 6)
            offer(std_w1, 2)
            offer(std_w2, 2)
            offer(bulk, 3)                 # > the 2/tick promote quota
            pump()
            if r_ % 8 == 0:
                check_conservation()
        st_mid = check_conservation()
        assert st_mid.queue_depth["standard"] > 0, \
            "sustained lane must keep STANDARD saturated"
        served_mid = dict(served_by)       # fair-share ratio is measured
        #                                    over the SUSTAINED phase —
        #                                    the drain below serves every
        #                                    backlog and dilutes it
        while sum(srv.stats().queue_depth.values()) \
                + sum(srv.stats().in_flight.values()):
            pump()                         # drain: clock keeps ticking
        st = check_conservation()
        return {"st": st, "served_by": served_by, "served_mid": served_mid,
                "accepted": accepted,
                "sids": dict(inter=inter, chatty=chatty, std_w1=std_w1,
                             std_w2=std_w2, bulk=bulk),
                "schedule": srv.schedule()}

    a, b = run_once(), run_once()
    # bit-reproducibility: same admitted schedule, same sheds, same
    # promotions, same refusals, same wait percentiles — twice
    assert a["schedule"] == b["schedule"], "sustained lane nondeterministic"
    for field in ("frames_submitted", "frames_served", "shed_expired",
                  "promoted", "rejected_full", "rejected_rate_limited",
                  "deadline_misses", "queue_wait_ms"):
        assert getattr(a["st"], field) == getattr(b["st"], field), field
    assert a["served_by"] == b["served_by"]

    st, ids = a["st"], a["sids"]
    w = st.queue_wait_ms
    # no starvation: BULK is served continuously through the aging lane
    # even though plain priority fill never reaches it (STANDARD stayed
    # saturated all run), and EVERY terminal wait — served OR shed — is
    # bounded by deadline + horizon + 2 tick windows, per class
    assert st.promoted["bulk"] > rounds // 2
    assert a["served_by"][ids["bulk"]] > rounds
    bulk_bound_ms = deadline_ms[B] + shed_horizon_ms + 2 * DT * 1e3
    assert w["bulk"]["max"] <= bulk_bound_ms, (w["bulk"], bulk_bound_ms)
    assert w["standard"]["max"] <= (deadline_ms[S] + shed_horizon_ms
                                    + 2 * DT * 1e3), w["standard"]
    # real load-shedding: offered BULK exceeds the promote quota (and
    # offered STANDARD exceeds its slots), so the excess expires past
    # the horizon and is dropped VISIBLY — never silently
    assert st.shed_expired["bulk"] > 0
    assert st.shed_expired["interactive"] == 0
    # INTERACTIVE rides priority fill: zero deadline misses, exact
    assert st.deadline_misses["interactive"] == 0
    assert w["interactive"]["max"] <= deadline_ms[I]
    # DRR over the sustained phase: the double-weight tenant gets ~2x
    # its equal-offered peer, and the chatty tenant is rate-capped to
    # parity with its peers despite offering 3x its budget
    mid = a["served_mid"]
    r21 = mid[ids["std_w2"]] / max(mid[ids["std_w1"]], 1)
    assert 1.6 <= r21 <= 2.4, f"weighted DRR share off 2:1: {r21:.2f}"
    assert st.rejected_rate_limited["standard"] > rounds
    assert mid[ids["chatty"]] <= 1.2 * mid[ids["std_w1"]]
    return {
        "rounds": rounds,
        "max_batch": max_batch,
        "tick_ms": DT * 1e3,
        "offered_per_tick": 16,
        "accepted": a["accepted"],
        "served": st.frames_served,
        "served_by_tenant": {name: a["served_by"][sid]
                             for name, sid in ids.items()},
        "served_by_tenant_sustained": {name: mid[sid]
                                       for name, sid in ids.items()},
        "standard_weight_ratio": r21,
        "promoted": st.promoted,
        "shed_expired": st.shed_expired,
        "rejected_full": st.rejected_full,
        "rejected_rate_limited": st.rejected_rate_limited,
        "deadline_misses": st.deadline_misses,
        "queue_wait_ms": w,
        "bulk_wait_bound_ms": bulk_bound_ms,
        "deadline_ms": {q.value: v for q, v in deadline_ms.items()},
        "reproducible": True,
    }


def run_all(*, quick=False, smoke=False):
    result = {"stream": {}, "overload": {}}
    rounds = 6 if smoke else (12 if quick else 24)
    m = bench_stream(N, rounds=rounds, repeats=2 if smoke else 3)
    result["stream"][N] = m
    fps = m["frames_per_s"]
    row(f"stream.seq_sync.N{N}", 1e6 / fps["seq_sync"],
        "sequential submit+tick, per-bucket-sync plane")
    row(f"stream.seq_async.N{N}", 1e6 / fps["seq_async"],
        "sequential submit+tick, single-sync plane")
    row(f"stream.server.N{N}", 1e6 / fps["server"],
        f"{m['speedup_vs_sync']:.2f}x vs sync loop, "
        f"{m['speedup_vs_async']:.2f}x vs single-sync loop, "
        f"bit-identical, {m['pipelined_tick_fraction']:.0%} ticks "
        "pipelined, 1 sync/tick")
    if m["speedup_vs_sync"] < 1.3:
        import sys
        print(f"# WARNING: stream server {m['speedup_vs_sync']:.2f}x vs "
              "the synchronous loop (< the 1.3x target) — overlap wins "
              "are regime-bound on shared-core CPU runners (docs/PERF.md)",
              file=sys.stderr)
    o = bench_overload(rounds=40 if smoke else 160)
    result["overload"] = o
    row("stream.overload.interactive_p95_wait",
        o["queue_wait_ms"]["interactive"]["p95"] * 1e3,
        f"ms*1e3; BULK p50 {o['queue_wait_ms']['bulk']['p50']:.1f}ms, "
        f"{o['preempted']['bulk']} preempted (conserved), "
        f"bulk miss rate {o['deadline_miss_rate']['bulk']:.2f}")
    u = bench_sustained(rounds=80 if smoke else 240)
    result["sustained"] = u
    row("stream.sustained.bulk_max_wait",
        u["queue_wait_ms"]["bulk"]["max"] * 1e3,
        f"ms*1e3 (bound {u['bulk_wait_bound_ms']:.0f}ms); "
        f"{u['promoted']['bulk']} promoted, "
        f"{u['shed_expired']['bulk']} shed visibly, "
        f"DRR 2:1 ratio {u['standard_weight_ratio']:.2f}, "
        "bit-reproducible")
    print("BENCH " + json.dumps({"bench": "stream_serve", **result}))
    return result


def write_bench_json(result, path="BENCH_stream.json"):
    """Machine-readable stream-serving trajectory (CI artifact — see
    docs/STREAMING.md for the schema)."""
    doc = {"bench": "stream_serve", "schema": 1,
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
