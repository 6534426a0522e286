"""Observability-plane benchmark: the tracing-off overhead budget,
exporter schema validation, and the flight-recorder exactness contract.

**Lane 1 — tracing-off overhead (< 2%, asserted).**  The telemetry
plane's pinned contract is that DISABLED tracing costs the hot path
nothing but attribute tests (docs/OBSERVABILITY.md): the submit path
pays one ``Tracer.maybe_begin`` miss, and every later hop pays one
``qf.trace is not None`` check.  An fps A/B against "the same code
without the branches" does not exist (the branches ARE the code) and a
2% fps delta is under CI noise anyway — so the lane measures the
off-path work DIRECTLY (microbenched per-frame: one miss + one
attribute test per stamp site) and asserts it is < 2% of the measured
per-frame serve time.  On any machine the miss is tens of nanoseconds
against a multi-hundred-microsecond frame, so a regression here means
someone put real work on the disabled path — exactly what the lane
exists to catch.  The fps of the SAME workload with ``sample=1.0`` is
reported beside it (tracing-ON cost is allowed to be visible; it buys
per-frame spans).

**Lane 2 — exporter schema (asserted).**  The off lane's server (and
its gateway, sharing the registry) exports through
``StreamServer.metrics()``; ``validate_prometheus`` must accept the
text (name/label grammar, TYPE-before-sample, no duplicate series) and
the sample count must cover the per-class serving counters.  A
registry JSONL snapshot is appended beside the run's own scalars
through ``MetricsLogger`` — the two sinks share one file format.

**Lane 3 — flight-recorder exactness (asserted).**  A deterministic
fake-clock overload sheds a known number of BULK frames; the
recorder's cumulative counts must reconstruct the stats-view shed
books exactly, and with ``sample=1.0`` every shed frame's span must
end at its ``shed`` stamp.  This is the stepped-clock miniature of the
cluster's automatic failover dump (tests/test_obs.py pins that end).

    PYTHONPATH=src python -m benchmarks.obs_bench [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import row
from benchmarks.gateway_serve import DEEP_KW, MixedKPolicy

N = 16
WARMUP_ROUNDS = 2
# hops that test ``qf.trace is not None`` on the serving path when
# tracing is off: enqueue, stage, admit, dispatch, collect (promote /
# preempt / shed only run on their anomaly paths)
_STAMP_SITES = 5
OVERHEAD_BUDGET = 0.02


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


def _server(cfg, params, n, *, sample):
    from repro.api import StreamSplitGateway
    from repro.serving import SchedulerCfg, StreamServer
    gw = StreamSplitGateway(cfg, params,
                            policy=MixedKPolicy(cfg.n_blocks),
                            capacity=n, window=16, qos_reserve=0)
    return StreamServer(gw, cfg=SchedulerCfg(max_batch=n),
                        queue_maxlen=1 << 16, trace_sample=sample)


def _off_path_ns():
    """Measured cost of the disabled tracing path, per frame: one
    ``maybe_begin`` miss at submit + one attribute test per stamp
    site.  Deterministic (pure Python, no device)."""
    from repro.obs import Tracer
    from repro.serving.queues import QueuedFrame
    tr = Tracer(0.0)
    qf = QueuedFrame(sid=1, frame=None, qos=None, seq=0, enq_s=0.0,
                     deadline_s=0.0)
    reps = 200_000
    t0 = time.perf_counter()
    for i in range(reps):
        tr.maybe_begin(1, i)
    begin_ns = (time.perf_counter() - t0) / reps * 1e9
    t0 = time.perf_counter()
    for _ in range(reps):
        if qf.trace is not None:
            raise AssertionError
    check_ns = (time.perf_counter() - t0) / reps * 1e9
    return begin_ns + _STAMP_SITES * check_ns, begin_ns, check_ns


def bench_overhead(n=N, *, rounds=16, repeats=3):
    """-> lane-1 dict: off-path ns/frame vs serve time/frame, plus the
    off/on fps A/B of the same stepped workload."""
    rounds_total = WARMUP_ROUNDS + rounds * repeats
    cfg, params, frames = _build(n, rounds_total)
    lanes = {"off": _server(cfg, params, n, sample=0.0),
             "on": _server(cfg, params, n, sample=1.0)}
    sids = {name: [srv.open_session().sid for _ in range(n)]
            for name, srv in lanes.items()}
    best = {name: float("inf") for name in lanes}

    def pump(name, t):
        srv = lanes[name]
        for i, sid in enumerate(sids[name]):
            srv.submit(sid, frames[t][i])
        srv.step()
        while srv.busy():
            srv.step()

    for t in range(WARMUP_ROUNDS):          # compile both paths
        for name in lanes:
            pump(name, t)
    t_base = WARMUP_ROUNDS
    for _ in range(repeats):                # interleaved best-of
        for name in lanes:
            t0 = time.perf_counter()
            for t in range(t_base, t_base + rounds):
                pump(name, t)
            best[name] = min(best[name], time.perf_counter() - t0)
        t_base += rounds
    fps = {name: n * rounds / b for name, b in best.items()}

    off = lanes["off"]
    assert off.tracer.started == 0 and off.recorder.traces() == [], \
        "sample=0.0 must allocate no spans"
    on = lanes["on"]
    assert on.tracer.started == on.tracer.finished == rounds_total * n

    off_ns, begin_ns, check_ns = _off_path_ns()
    frame_ns = 1e9 / fps["off"]
    frac = off_ns / frame_ns
    assert frac < OVERHEAD_BUDGET, (
        f"disabled tracing costs {frac:.2%} of a frame "
        f"({off_ns:.0f}ns of {frame_ns:.0f}ns) — budget "
        f"{OVERHEAD_BUDGET:.0%}")
    return {
        "n": n,
        "frames_per_s": fps,
        "tracing_on_cost": 1.0 - fps["on"] / fps["off"],
        "off_path_ns_per_frame": off_ns,
        "off_maybe_begin_ns": begin_ns,
        "off_attr_check_ns": check_ns,
        "off_path_fraction_of_frame": frac,
        "overhead_budget": OVERHEAD_BUDGET,
        "traces_on": on.tracer.finished,
        "server_off": off,                 # lane 2 exports this stack
    }


def bench_export(srv):
    """-> lane-2 dict: Prometheus text validated + snapshot shape."""
    from repro.obs import registry_snapshot, validate_prometheus
    text = srv.metrics()
    n_samples = validate_prometheus(text)   # raises on any violation
    assert n_samples >= 20, f"suspiciously thin export: {n_samples}"
    for must in ("stream_frames_served", "stream_frames_submitted",
                 "stream_queue_wait_ms_count", "gateway_stage_ewma_ms"):
        assert must in text, f"export lost {must}"
    snap = registry_snapshot(srv.registry)
    assert {m["kind"] for m in snap["metrics"]} >= {"counter", "gauge",
                                                    "histogram"}
    return {"prometheus_samples": n_samples,
            "registry_metrics": len(snap["metrics"]),
            "prometheus_valid": True}


def bench_recorder(*, rounds=24, max_batch=4):
    """-> lane-3 dict: fake-clock overload; dump counts == stats books,
    exactly."""
    from repro.api import FrameRequest, QoSClass, StreamSplitGateway
    from repro.api.policies import FixedKPolicy
    from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder
    from repro.serving import SchedulerCfg, StreamServer
    B = QoSClass.BULK
    cfg = AudioEncCfg(**DEEP_KW)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))

    class _FakeClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    clock = _FakeClock()
    gw = StreamSplitGateway(cfg, params,
                            policy=FixedKPolicy(cfg.n_blocks, 4),
                            capacity=4, window=16, qos_reserve=0,
                            clock=clock)
    srv = StreamServer(gw, cfg=SchedulerCfg(
        max_batch=max_batch, deadline_ms={B: 100.0},
        shed_horizon_ms=200.0, max_wait_ms={B: None}),
        clock=clock, trace_sample=1.0)
    sid = srv.open_session(qos=B).sid
    rng = np.random.default_rng(3)
    mels = [rng.normal(size=(cfg.frames, cfg.n_mels)).astype(np.float32)
            for _ in range(8)]
    # each round: offer 2x the batch, serve one tick, jump the clock a
    # full horizon — everything still queued at the next admit sheds
    for r in range(rounds):
        for j in range(2 * max_batch):
            srv.submit(sid, FrameRequest(t=r * 2 * max_batch + j,
                                         mel=mels[j % 8]))
        srv.step()
        clock.t += 0.5
    while srv.busy():
        srv.step()
        clock.t += 0.5
    st = srv.stats()
    dump = srv.dump_trace(reason="obs_bench")
    assert st.shed_expired["bulk"] > 0, "overload lane must shed"
    assert dump["counts"]["shed"] == st.shed_expired["bulk"], \
        "flight recorder disagrees with the conservation books"
    # a shed counts as the deadline miss it already was in the stats
    # view, but records as a "shed" event — the two ledgers partition
    assert (dump["counts"].get("deadline_miss", 0)
            + dump["counts"]["shed"]) == st.deadline_misses["bulk"]
    shed_spans = [t for t in dump["traces"]
                  if t["events"][-1]["name"] == "shed"]
    assert len(shed_spans) == st.shed_expired["bulk"], \
        "every shed frame's span must end at its shed stamp"
    return {"rounds": rounds,
            "shed": st.shed_expired["bulk"],
            "served": st.frames_served["bulk"],
            "dump_counts": dump["counts"],
            "evicted_events": dump["evicted_events"],
            "counts_exact": True}


def run_all(*, quick=False, smoke=False):
    result = {}
    rounds = 6 if smoke else (10 if quick else 16)
    o = bench_overhead(N, rounds=rounds, repeats=2 if smoke else 3)
    srv_off = o.pop("server_off")
    result["overhead"] = o
    row("obs.off_path_ns_per_frame", o["off_path_ns_per_frame"] * 1e-3,
        f"{o['off_path_fraction_of_frame']:.4%} of a frame "
        f"(budget {o['overhead_budget']:.0%}), asserted")
    row(f"obs.tracing_on.N{N}", 1e6 / o["frames_per_s"]["on"],
        f"tracing-on cost {o['tracing_on_cost']:.1%} of throughput, "
        f"{o['traces_on']} spans retired")
    e = bench_export(srv_off)
    result["export"] = e
    row("obs.prometheus_samples", e["prometheus_samples"],
        "schema-validated exposition samples from one serving stack")
    with srv_off.queues.cond:
        pass                               # stack idle; nothing to join
    r = bench_recorder(rounds=8 if smoke else 24)
    result["recorder"] = r
    row("obs.recorder_shed", r["shed"],
        f"dump counts == stats books exactly; "
        f"{r['evicted_events']} ring-evicted events still counted")
    # one JSONL line carrying the registry beside the bench scalars —
    # the composed-sinks pattern docs/OBSERVABILITY.md describes
    from repro.obs import write_jsonl
    from repro.runtime.metrics import MetricsLogger
    with MetricsLogger("BENCH_obs.jsonl", window=8) as m:
        m.log(0, off_path_ns=o["off_path_ns_per_frame"],
              fps_off=o["frames_per_s"]["off"],
              fps_on=o["frames_per_s"]["on"])
    write_jsonl(srv_off.registry, "BENCH_obs.jsonl", step=1)
    print("BENCH " + json.dumps({"bench": "obs", **result}))
    return result


def write_bench_json(result, path="BENCH_obs.json"):
    """Machine-readable observability trajectory (CI artifact — see
    docs/OBSERVABILITY.md for the schema)."""
    doc = {"bench": "obs", "schema": 1,
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
