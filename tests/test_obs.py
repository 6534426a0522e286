"""Unified telemetry plane (repro.obs): the quantile sketch's two
regimes pinned against ``numpy.percentile``, registry typing and
views, deterministic per-frame span tracing across pipelined ticks /
live migration / journal replay, the zero-allocation tracing-off fast
path, flight-recorder exactness under eviction, exporter schema
validation, and the stats-view bit-parity + conservation contracts.
"""
import json

import jax
import numpy as np
import pytest

from repro.api import FrameRequest, QoSClass
from repro.cluster import FailureInjector, GatewayCluster
from repro.obs import (Counter, FlightRecorder, Gauge, Histogram,
                       MetricsRegistry, QuantileSketch, Tracer,
                       registry_snapshot, sampled, to_prometheus,
                       validate_prometheus, write_jsonl)
from repro.runtime.metrics import MetricsLogger
from repro.serving import (QoSQueues, SchedulerCfg, StreamServer,
                           TickScheduler)

from test_cluster import (FakeClock, _assert_conserved, _gw, _req,
                          _server)

I, S, B = QoSClass.INTERACTIVE, QoSClass.STANDARD, QoSClass.BULK
ALL = ("interactive", "standard", "bulk")


@pytest.fixture(scope="module")
def params():
    from repro.models.audio_encoder import init_audio_encoder
    from test_cluster import CFG
    return init_audio_encoder(CFG, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# QuantileSketch: exact regime is numpy, binned regime is bounded
# ---------------------------------------------------------------------------

def test_sketch_exact_regime_bit_identical_to_numpy():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=3.0, sigma=1.2, size=1000)
    sk = QuantileSketch(exact_cap=4096)
    for x in xs:
        sk.observe(x)
    assert sk.exact
    for q in (0, 10, 50, 90, 95, 99, 100):
        assert sk.quantile(q) == float(np.percentile(xs, q))   # bitwise
    assert sk.summary()["max"] == float(xs.max())
    assert sk.count == 1000 and sk.total == pytest.approx(xs.sum())


def test_sketch_insertion_order_never_matters():
    rng = np.random.default_rng(8)
    xs = rng.exponential(50.0, size=500)
    a, b = QuantileSketch(exact_cap=100), QuantileSketch(exact_cap=100)
    for x in xs:
        a.observe(x)
    for x in xs[::-1]:
        b.observe(x)
    assert not a.exact and not b.exact       # both in the binned regime
    for q in (50, 95, 99):
        assert a.quantile(q) == b.quantile(q)
    assert (a.vmin, a.vmax, a.count) == (b.vmin, b.vmax, b.count)


def test_sketch_binned_regime_error_bounded_by_growth():
    """Past ``exact_cap`` quantiles come from growth-ratio log bins:
    relative error stays under the bin ratio on seeded heavy-tailed
    data, and min/max/count/sum stay EXACT."""
    rng = np.random.default_rng(9)
    xs = rng.lognormal(mean=2.0, sigma=1.0, size=20_000)
    sk = QuantileSketch(exact_cap=64, growth=1.1)
    for x in xs:
        sk.observe(x)
    assert not sk.exact
    for q in (50, 90, 95, 99):
        ref = float(np.percentile(xs, q))
        assert abs(sk.quantile(q) - ref) / ref < 0.1    # ~growth - 1
    assert sk.vmin == xs.min() and sk.vmax == xs.max()
    assert sk.count == len(xs)
    assert sk.total == pytest.approx(xs.sum())


def test_sketch_single_sample_and_empty():
    sk = QuantileSketch()
    assert sk.summary() == {"p50": 0.0, "p95": 0.0, "mean": 0.0,
                            "max": 0.0}
    sk.observe(250.0)
    s = sk.summary()
    assert s["p50"] == s["p95"] == s["max"] == 250.0


# ---------------------------------------------------------------------------
# Registry: typed get-or-create, label keying, views
# ---------------------------------------------------------------------------

def test_registry_get_or_create_idempotent_and_typed():
    r = MetricsRegistry()
    c = r.counter("x_total", qos="bulk")
    assert r.counter("x_total", qos="bulk") is c
    assert r.counter("x_total", qos="interactive") is not c
    with pytest.raises(ValueError):
        r.gauge("x_total", qos="bulk")       # same name, wrong type
    with pytest.raises(ValueError):
        r.histogram("x_total", qos="bulk")
    assert r.value("x_total", qos="bulk") == 0
    assert r.value("never_created") == 0     # view convention
    c.inc(3)
    assert r.value("x_total", qos="bulk") == 3
    assert len(r) == 2


def test_counter_accepts_negative_inc_for_ledger_relocation():
    c = Counter("moved", ())
    c.inc(5)
    c.inc(-2)                                # migration withdraws frames
    assert c.value == 3


def test_gauge_ewma_first_sample_seeds():
    g = Gauge("lat", ())
    assert g.ewma(10.0) == 10.0              # no zero-pull warmup
    v = g.ewma(20.0, alpha=0.5)
    assert v == 15.0
    g.try_set_max(12.0)
    assert g.value == 15.0
    g.try_set_max(99.0)
    assert g.value == 99.0


def test_histogram_through_registry():
    r = MetricsRegistry()
    h = r.histogram("wait_ms", qos="bulk")
    assert isinstance(h, Histogram)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4
    assert h.summary()["max"] == 4.0
    assert h.quantile(50) == float(np.percentile([1, 2, 3, 4], 50))


# ---------------------------------------------------------------------------
# Sampling: deterministic, member/replay-stable
# ---------------------------------------------------------------------------

def test_sampled_is_deterministic_and_edge_exact():
    for sid in range(20):
        for t in range(20):
            assert sampled(sid, t, 1.0) is True
            assert sampled(sid, t, 0.0) is False
            assert sampled(sid, t, 0.5) == sampled(sid, t, 0.5)
    hits = sum(sampled(sid, t, 0.25)
               for sid in range(50) for t in range(50))
    assert 0.15 < hits / 2500 < 0.35         # hash is roughly uniform


def test_tracer_off_allocates_nothing():
    tr = Tracer(0.0)
    assert not tr.enabled
    assert tr.maybe_begin(1, 2) is None and tr.started == 0
    tr.finish(None)                          # no-ops on None
    tr.retire(None)
    assert tr.finished == 0


# ---------------------------------------------------------------------------
# FlightRecorder: rings evict, counts never do
# ---------------------------------------------------------------------------

def test_recorder_counts_exact_under_ring_eviction():
    rec = FlightRecorder(event_capacity=4, clock=lambda: 1.5)
    for i in range(10):
        rec.record("shed", sid=0, t=i)
    rec.record("failover", member="a")
    assert rec.counts() == {"shed": 10, "failover": 1}
    assert len(rec.events()) == 4            # ring is bounded
    assert len(rec.events("shed")) == 3
    d = rec.dump(reason="test")
    assert d["reason"] == "test" and d["t_s"] == 1.5
    assert d["counts"]["shed"] == 10         # exact despite eviction
    assert d["evicted_events"] == 7
    json.dumps(d)                            # dump is JSON-able


# ---------------------------------------------------------------------------
# Exporters: Prometheus text format + JSONL snapshots
# ---------------------------------------------------------------------------

def _loaded_registry():
    r = MetricsRegistry()
    r.counter("stream_frames_served", qos="bulk").inc(7)
    r.counter("stream_frames_served", qos="interactive").inc(2)
    r.gauge("gateway_stage_ewma_ms", stage="tick").set(1.25)
    h = r.histogram("stream_queue_wait_ms", qos="bulk")
    for v in (10.0, 20.0, 400.0):
        h.observe(v)
    return r


def test_prometheus_export_validates_and_round_trips():
    text = to_prometheus(_loaded_registry())
    n = validate_prometheus(text)            # raises on any violation
    assert n >= 8                            # 2 counters, 1 gauge, summary
    assert 'stream_frames_served{qos="bulk"} 7' in text
    assert 'quantile="0.95"' in text
    assert "stream_queue_wait_ms_count" in text
    assert "stream_queue_wait_ms_max" in text


def test_prometheus_validator_rejects_garbage():
    with pytest.raises(ValueError):
        validate_prometheus("9bad_name 1\n")
    with pytest.raises(ValueError):
        validate_prometheus('ok{label="x"} notanumber\n')
    with pytest.raises(ValueError):          # duplicate series
        validate_prometheus("# TYPE a counter\na 1\na 2\n")


def test_jsonl_snapshot_appends_parseable_lines(tmp_path):
    p = tmp_path / "metrics.jsonl"
    r = _loaded_registry()
    write_jsonl(r, p, step=0, clock=lambda: 5.0)
    write_jsonl(r, p, step=1, clock=lambda: 6.0)
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert [x["step"] for x in lines] == [0, 1]
    snap = registry_snapshot(r, clock=lambda: 5.0)
    names = {m["name"] for m in snap["metrics"]}
    assert "stream_queue_wait_ms" in names and snap["t_s"] == 5.0


# ---------------------------------------------------------------------------
# MetricsLogger (runtime/metrics.py): the satellite fix
# ---------------------------------------------------------------------------

def test_metrics_logger_context_manager_clock_and_mean(tmp_path):
    p = tmp_path / "train.jsonl"
    with MetricsLogger(str(p), window=2, clock=lambda: 9.0) as m:
        m.log(0, loss=4.0)
        m.log(1, loss=2.0)
        m.log(2, loss=1.0)
        assert m.mean("loss") == 1.5         # rolling window of 2
        assert np.isnan(m.mean("nope"))      # lookups do not pollute
        assert "nope" not in m.buf
    assert m._f is None                      # closed by __exit__
    rows = [json.loads(x) for x in p.read_text().splitlines()]
    assert len(rows) == 3 and all(r["t"] == 9.0 for r in rows)


# ---------------------------------------------------------------------------
# Serving integration: spans across pipelined ticks (fake clock)
# ---------------------------------------------------------------------------

# submit -> enqueue -> [promote] -> stage -> admit -> dispatch ->
# collect -> serve; shed terminates, migrate hops continue
_ORDER = ("submit", "enqueue", "stage", "admit", "dispatch", "collect",
          "serve")


def _assert_span_order(trace):
    names = [n for n in trace.names() if n in _ORDER]
    assert names == [n for n in _ORDER if n in names]
    stamps = [e[1] for e in trace.events]
    assert stamps == sorted(stamps)          # clock-monotone


def test_trace_spans_ordered_across_pipelined_ticks(params):
    clock = FakeClock()
    srv = _server(params, clock, max_batch=4, trace_sample=1.0)
    sids = [srv.open_session(qos=q).sid for q in (I, S, B)]
    n = 0
    for t in range(4):
        for sid in sids:
            srv.submit(sid, _req(sid, t))
            n += 1
        clock.advance(0.01)
        srv.step()
    while srv.busy():
        clock.advance(0.01)
        srv.step()
    st = srv.stats()
    assert st.pipelined_ticks > 0            # the overlap really happened
    traces = srv.recorder.traces()
    assert len(traces) == n                  # sample=1.0: all retired
    assert srv.tracer.started == srv.tracer.finished == n
    for tr in traces:
        _assert_span_order(tr)
        assert tr.find("submit") is not None
        assert tr.find("serve") is not None
        d = tr.find("dispatch")
        assert d is not None and "k" in d[2] and "shard" in d[2]
    # deterministic span math on the fake clock: submit -> serve is a
    # whole number of 10ms steps
    ms = traces[0].span_ms("submit", "serve")
    assert ms == pytest.approx(round(ms / 10) * 10, abs=1e-6)


def test_trace_sampling_subset_matches_hash(params):
    clock = FakeClock()
    srv = _server(params, clock, max_batch=8, trace_sample=0.5)
    sid = srv.open_session(qos=S).sid
    want = set()
    for t in range(20):
        srv.submit(sid, _req(sid, t))
        if sampled(sid, t, 0.5):
            want.add(t)
        clock.advance(0.01)
        srv.step()
    while srv.busy():
        clock.advance(0.01)
        srv.step()
    got = {tr.t for tr in srv.recorder.traces()}
    assert got == want and 0 < len(got) < 20


def test_tracing_off_is_the_zero_allocation_path(params):
    clock = FakeClock()
    srv = _server(params, clock, max_batch=4)      # default: off
    sid = srv.open_session(qos=S).sid
    for t in range(3):
        srv.submit(sid, _req(sid, t))
        clock.advance(0.01)
        srv.step()
    while srv.busy():
        srv.step()
    assert srv.tracer.started == 0 and srv.recorder.traces() == []
    with srv.queues.cond:                    # nothing carries a trace
        assert all(qf.trace is None
                   for cq in srv.queues.by_class.values()
                   for qf in cq.q)
    assert srv.served_total == 3             # and serving still works


def test_trace_shed_terminates_span_into_recorder(params):
    clock = FakeClock()
    srv = StreamServer(
        _gw(params, clock, capacity=2),
        cfg=SchedulerCfg(max_batch=2, deadline_ms={B: 100.0},
                         shed_horizon_ms=200.0, max_wait_ms={B: None}),
        clock=clock, trace_sample=1.0)
    sid = srv.open_session(qos=B).sid
    for t in range(6):
        srv.submit(sid, _req(sid, t))
    srv.step()                               # admits 2, stages 2
    clock.t = 10.0
    srv.step()                               # sheds the 2 queued frames
    while srv.busy():
        srv.step()
    shed_traces = [tr for tr in srv.recorder.traces()
                   if tr.find("shed") is not None]
    assert len(shed_traces) == 2
    for tr in shed_traces:
        assert tr.find("serve") is None      # shed IS the terminal
        assert tr.events[-1][0] == "shed"
    # the recorder's anomaly ledger agrees with the stats view, exactly
    st = srv.stats()
    assert srv.recorder.counts()["shed"] == st.shed_expired["bulk"] == 2
    ev = srv.recorder.events("shed")[0]
    assert ev["sid"] == sid and "waited_ms" in ev


# ---------------------------------------------------------------------------
# Migration + journal replay: trace continuity
# ---------------------------------------------------------------------------

def test_trace_survives_live_migration_with_original_submit(params):
    clock = FakeClock(t=1.0)
    src = _server(params, clock, max_batch=4, trace_sample=1.0)
    dst = _server(params, clock, max_batch=4, trace_sample=1.0)
    sid = src.open_session(qos=S).sid
    for t in range(3):
        src.submit(sid, _req(sid, t))       # queued, never stepped
    clock.advance(0.5)
    snap = src.export_session(sid)
    assert all(s.trace is not None for s in snap.server.queued)
    info = dst.import_session(snap)
    while dst.busy():
        clock.advance(0.01)
        dst.step()
    traces = dst.recorder.traces()
    assert len(traces) == 3
    for tr in traces:
        names = tr.names()
        for hop in ("submit", "enqueue", "migrate_out", "migrate_in",
                    "serve"):
            assert hop in names, (hop, names)
        assert names.index("migrate_out") < names.index("migrate_in")
        assert tr.find("submit")[1] == 1.0   # ORIGINAL submit stamp
        assert tr.find("migrate_out")[1] == 1.5
        assert tr.find("migrate_in")[2]["sid"] == info.sid
    # src retired nothing: the spans moved, they did not end there
    assert src.recorder.traces() == []


def test_cluster_failover_dump_and_replay_trace_adoption(params):
    """Seeded overload + member kill: the automatic flight-recorder
    dump reconstructs the failover/failure counts exactly, and frames
    recovered by journal replay carry adopted traces that begin at the
    ``replay`` hop with their ORIGINAL enqueue timestamp."""
    clock = FakeClock()
    members = {"a": _server(params, clock, max_batch=4,
                            trace_sample=1.0),
               "b": _server(params, clock, max_batch=4,
                            trace_sample=1.0)}
    cl = GatewayCluster(members, seed=3, snapshot_every=2,
                        replicate=True, journal_flush_every=1,
                        injectors={"a": FailureInjector(fail_at=(6,))},
                        timer=clock)
    infos = [cl.open_session(qos=S) for _ in range(4)]
    for t in range(10):
        for i in infos:
            cl.submit(i.sid, _req(i.sid, t))
        clock.advance(0.01)
        cl.step()
        _assert_conserved(cl.stats())
    cl.pump()
    st = cl.stats()
    assert st.failures == 1 and st.failovers > 0
    # -- the acceptance contract: dump == books, exactly ------------------
    dump = cl.dump_trace()
    assert dump["counts"]["failover"] == st.failovers
    assert dump["counts"]["member_failed"] == st.failures
    assert dump["counts"].get("journal_replay", 0) > 0
    auto = cl.failover_dumps
    assert len(auto) == 1 and auto[0]["reason"] == "member_failed:a"
    assert auto[0]["counts"]["member_failed"] == 1
    # every failover event names source and destination
    for ev in cl.recorder.events("failover"):
        assert ev["src"] == "a" and ev["dst"] == "b"
    # -- replayed frames: adopted spans, original enqueue ----------------
    replayed = [tr for tr in members["b"].recorder.traces()
                if tr.names() and tr.names()[0] == "replay"]
    assert len(replayed) == st.replayed_frames > 0
    for tr in replayed:
        assert tr.events[0][2]["member"] == "b"
        assert "enq_s" in tr.events[0][2]    # the original ledger
        assert tr.names()[-1] == "serve"     # recovered AND served
    # cluster books and prometheus export agree
    text = cl.metrics()
    validate_prometheus(text)
    assert f"cluster_failovers {st.failovers}" in text


# ---------------------------------------------------------------------------
# Stats views: bit-parity, conservation, EWMA stage timings, signals
# ---------------------------------------------------------------------------

def _run_workload(params, seed=0):
    clock = FakeClock()
    srv = _server(params, clock, max_batch=4)
    sids = [srv.open_session(qos=q).sid for q in (I, S, B)]
    for t in range(6):
        for sid in sids:
            srv.submit(sid, _req(sid, t))
        clock.advance(0.01)
        srv.step()
    while srv.busy():
        clock.advance(0.01)
        srv.step()
    return srv


def test_stats_views_bit_reproducible_and_conserved(params):
    a, b = _run_workload(params), _run_workload(params)
    sa, sb = a.stats(), b.stats()
    # registry-backed views are plain dicts, equal across reruns
    assert sa.frames_submitted == sb.frames_submitted
    assert sa.frames_served == sb.frames_served
    assert dict(sa.deadline_misses) == dict(sb.deadline_misses)
    assert sa.queue_wait_ms == sb.queue_wait_ms       # sketch: exact
    for c in ALL:                                     # conservation
        assert sa.frames_submitted[c] == (
            sa.frames_served[c] + sa.queue_depth[c] + sa.in_flight[c]
            + sa.shed_expired[c])
    # wait percentiles really are numpy.percentile in the exact regime
    h = a.scheduler.wait_hist["standard"]
    assert h.sketch.exact
    assert sa.queue_wait_ms["standard"]["p95"] == h.quantile(95)


def test_stage_ewma_always_on_without_profile(params):
    srv = _run_workload(params)
    R = srv.registry
    # the tick span's EWMA stays (resource_signals feeds it to policies)
    tick = R.get("gateway_stage_ewma_ms", stage="tick")
    assert tick is not None and tick.value > 0.0
    assert srv.resource_signals().stage_ewma_ms == tick.value
    # launch and collect are phase counters now, not EWMA gauges
    for stage in ("launch", "collect"):
        assert R.get("gateway_stage_ewma_ms", stage=stage) is None
        assert R.value("gateway_phase_calls_total", phase=stage) \
            == srv.stats().ticks
    # the profile knob is a debug detail now, not the only timing source
    assert srv.gateway.last_profile is None


def test_server_metrics_export_and_resource_signals(params):
    srv = _run_workload(params)
    text = srv.metrics()
    validate_prometheus(text)
    assert "stream_frames_served" in text
    assert "gateway_frames_total" in text or "gateway_" in text
    sig = srv.resource_signals()
    assert sig.queue_depth == 0              # fully drained
    obs = sig.as_observation()
    assert obs.shape == (5,) and obs.dtype == np.float32
    assert np.all(obs >= 0.0) and np.all(obs <= 1.0)
    assert sig.throughput_fps > 0.0
    st = srv.stats()
    assert sig.wait_p95_ms == max(
        w["p95"] for w in st.queue_wait_ms.values())


def test_scheduler_wait_sketch_matches_numpy_on_known_waits():
    """Satellite (b): the per-class wait-sample lists are gone; the
    sketch behind ``wait_percentiles`` reproduces ``numpy.percentile``
    exactly for deterministic fake-clock waits."""
    qs = QoSQueues(maxlen=64)
    sched = TickScheduler(SchedulerCfg(max_batch=64,
                                       max_wait_ms={B: None}))
    f = FrameRequest(t=0, mel=np.zeros((2, 2), np.float32))
    waits = [10.0, 20.0, 40.0, 80.0, 160.0]
    for i, w in enumerate(waits):
        qs.submit(i, f, B, now=1.0 - w * 1e-3, deadline_s=99.0)
    sched.stage(qs)
    batch = sched.admit(qs, 1.0)
    assert len(batch) == len(waits)
    got = sched.wait_percentiles()["bulk"]
    # the expectation reproduces the scheduler's own float arithmetic
    # ((now - enq_s) * 1e3) — bit-identity, not approximation
    arr = np.asarray([(1.0 - (1.0 - w * 1e-3)) * 1e3 for w in waits])
    assert got["p50"] == float(np.percentile(arr, 50))
    assert got["p95"] == float(np.percentile(arr, 95))
    assert got["max"] == float(arr.max())
    assert got["mean"] == pytest.approx(arr.mean())
