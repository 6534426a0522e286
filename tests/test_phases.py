"""Tick-phase spans and counters (``repro.obs.Phases``): each phase of
the gateway and the serving loop is counted once where its work
happens, on the injected clock; a phase of 50 ms or more is counted
slow and leaves one ``slow_phase`` event (``stream.wait`` never does);
the stats views equal the registry counters; and every span reaches a
host plane of the profiler's trace.
"""
import glob
import threading
import time

import jax
import pytest

from repro.api import ShardedFleetBackend
from repro.api.gateway import PHASES as GW_PHASES
from repro.obs import SLOW_PHASE_S, FlightRecorder, MetricsRegistry, Phases
from repro.serving.server import PHASES as STREAM_PHASES

from test_cluster import CFG, FakeClock, _gw, _head, _req, _server

LAUNCH_PARTS = ("decide", "stage_h2d", "dispatch", "account")


@pytest.fixture(scope="module")
def params():
    from repro.models.audio_encoder import init_audio_encoder
    return init_audio_encoder(CFG, jax.random.PRNGKey(0))


class SteppingClock:
    """Each read is ``step`` seconds after the last, so every phase
    lasts as many steps as the clock reads it spans."""

    def __init__(self, step):
        self.step, self.n = step, 0
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            self.n += 1
            return self.n * self.step


def _calls(R, prefix, phase):
    return R.value(f"{prefix}_phase_calls_total", phase=phase)


def _ns(R, prefix, phase):
    return R.value(f"{prefix}_phase_ns_total", phase=phase)


@pytest.mark.parametrize("plane", ["single", "sharded"])
def test_each_phase_counted_once_per_tick(params, plane):
    clock = SteppingClock(1e-3)
    head_init, head_apply = _head()
    kw = dict(refine_every=2)
    if plane == "sharded":
        kw.update(shard_dispatch=True, backend=ShardedFleetBackend(
            capacity=8, window=8, dim=CFG.d_embed, head_init=head_init,
            head_apply=head_apply))
    else:
        kw.update(head_init=head_init, head_apply=head_apply)
    gw = _gw(params, clock, **kw)
    assert gw.shard_dispatch == (plane == "sharded")
    sids = [gw.open_session().sid for _ in range(5)]
    n_ticks = 4
    for t in range(n_ticks):
        for sid in sids:
            gw.submit(sid, _req(sid, t, label=t % 4))
        gw.tick()
    R = gw.registry
    for phase in ("launch", "collect", "sync") + LAUNCH_PARTS:
        assert _calls(R, "gateway", phase) == n_ticks, phase
    assert _calls(R, "gateway", "refine") == n_ticks // 2
    assert _calls(R, "gateway", "open_session") == len(sids)
    # the launch sub-phases lie inside the launch span, sync and refine
    # inside the collect span: their sums cannot exceed their parent
    parts = sum(_ns(R, "gateway", p) for p in LAUNCH_PARTS)
    assert 0 < parts < _ns(R, "gateway", "launch")
    assert 0 < (_ns(R, "gateway", "sync") + _ns(R, "gateway", "refine")
                < _ns(R, "gateway", "collect"))


def test_stream_phases_counted_per_step(params):
    clock = FakeClock()
    srv = _server(params, clock, max_batch=4)
    sids = [srv.open_session().sid for _ in range(3)]
    steps = 0
    for t in range(4):
        for sid in sids:
            srv.submit(sid, _req(sid, t))
        clock.advance(0.01)
        srv.step()
        steps += 1
    while srv.busy():
        clock.advance(0.01)
        srv.step()
        steps += 1
    R, st = srv.registry, srv.stats()
    for phase in ("step", "admit", "stage"):
        assert _calls(R, "stream", phase) == steps, phase
    assert _calls(R, "stream", "deliver") == st.ticks
    assert _calls(R, "gateway", "launch") == st.gateway.ticks == st.ticks
    assert _calls(R, "stream", "wait") == 0      # no serving thread ran
    # the fake clock stands still inside a step: every phase reads 0 ns
    assert all(s == 0.0 for _, s, _ in st.phases.values())


def test_slow_phase_event_and_counter():
    clock, R = FakeClock(), MetricsRegistry()
    rec = FlightRecorder(clock=clock)
    ticks = R.counter("stream_ticks")
    ph = Phases(R, "stream", ("admit", "wait"), clock=clock, recorder=rec,
                tick=ticks, exempt=("wait",))
    ticks.inc(3)
    span = ph.begin("admit")
    clock.advance(SLOW_PHASE_S * 0.98)             # just under: not slow
    ph.end(span)
    span = ph.begin("admit")
    clock.advance(0.0625)
    ph.end(span)
    t_slow = clock.t
    span = ph.begin("wait")
    clock.advance(1.0)                             # idles by design
    ph.end(span)
    (ev,) = rec.events("slow_phase")
    assert ev == {"kind": "slow_phase", "t_s": t_slow,
                  "phase": "stream.admit", "ms": pytest.approx(62.5),
                  "tick": 3}
    assert rec.counts() == {"slow_phase": 1}
    assert ph.view()["stream.admit"] == (2, 0.1115, 1)
    assert R.value("stream_phase_ns_total", phase="admit") == 111_500_000
    assert ph.view()["stream.wait"] == (1, 1.0, 0)


def test_slow_decide_in_a_served_tick_records_one_event(params):
    clock = FakeClock()
    srv = _server(params, clock, max_batch=4)
    gw = srv.gateway
    decide = gw.policy.decide

    def slow_decide(obs):
        clock.advance(0.06)
        return decide(obs)

    gw.policy.decide = slow_decide
    sid = srv.open_session().sid
    srv.submit(sid, _req(sid, 0))
    srv.step()
    srv.step()
    events = srv.recorder.events("slow_phase")
    mine = [e for e in events if e["phase"] == "gateway.decide"]
    assert len(mine) == 1
    assert mine[0]["ms"] == pytest.approx(60.0)
    slow = {name: s
            for name, (_, _, s) in srv.stats().gateway.phases.items()}
    assert slow["gateway.decide"] == 1
    for p in ("stage_h2d", "dispatch", "account", "collect", "sync"):
        assert slow[f"gateway.{p}"] == 0, p
    # its parents ran as long, and are slow too, each once
    assert sorted(e["phase"] for e in events) == [
        "gateway.decide", "gateway.launch", "stream.step"]


def test_stream_wait_never_slow(params):
    clock = SteppingClock(0.1)        # every wait spans 100 ms of clock
    srv = _server(params, clock, max_batch=4)
    srv.start()
    try:
        deadline = time.monotonic() + 10.0
        while (srv.registry.value("stream_phase_calls_total", phase="wait")
               < 3 and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        srv.stop()
    calls, secs, slow = srv.stats().phases["stream.wait"]
    assert calls >= 3 and secs == pytest.approx(0.1 * calls)
    assert slow == 0
    assert not [e for e in srv.recorder.events("slow_phase")
                if e["phase"] == "stream.wait"]


def test_phase_views_match_registry(params):
    clock = SteppingClock(1e-4)
    srv = _server(params, clock, max_batch=4)
    sids = [srv.open_session().sid for _ in range(3)]
    for t in range(3):
        for sid in sids:
            srv.submit(sid, _req(sid, t))
        srv.step()
    while srv.busy():
        srv.step()
    st, R = srv.stats(), srv.registry
    views = {"gateway": st.gateway.phases, "stream": st.phases}
    names = {"gateway": GW_PHASES, "stream": STREAM_PHASES}
    assert len(GW_PHASES) + len(STREAM_PHASES) == 14
    for prefix, view in views.items():
        assert set(view) == {f"{prefix}.{p}" for p in names[prefix]}
        for p in names[prefix]:
            want = (R.value(f"{prefix}_phase_calls_total", phase=p),
                    R.value(f"{prefix}_phase_ns_total", phase=p) / 1e9,
                    R.value(f"{prefix}_phase_slow_total", phase=p))
            assert view[f"{prefix}.{p}"] == want, p
            assert isinstance(want[0], int) and isinstance(
                R.value(f"{prefix}_phase_ns_total", phase=p), int)
    assert st.phases["stream.step"][0] > 0


def test_phase_spans_reach_the_profiler_trace(params, tmp_path):
    from jax.profiler import ProfileData
    srv = _server(params, FakeClock(), max_batch=4)
    sid = srv.open_session().sid
    srv.submit(sid, _req(sid, 0))      # compile outside the trace
    srv.step()
    srv.step()
    with jax.profiler.trace(str(tmp_path)):
        sid2 = srv.open_session().sid
        for t in (1, 2):
            srv.submit(sid, _req(sid, t))
            srv.submit(sid2, _req(sid2, t))
            srv.step()
        srv.step()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    names = {e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    want = {f"gateway.{p}" for p in GW_PHASES if p != "refine"} | {
        f"stream.{p}" for p in STREAM_PHASES if p != "wait"}
    assert want <= names, sorted(want - names)
