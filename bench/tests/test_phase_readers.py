"""The readers of the program's tick-phase counters, on stand-in
``w0``/``w1`` snapshots and on a real ``StreamServer``'s; snapshots
without phases (a program that has none) read ``None``."""
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

import harness

ROOT = os.path.dirname(harness.BENCH)
READERS = {
    # name: (unit, value on the stand-in snapshots below)
    "decide_ms.mean": ("ms", 0.25),
    "stage_h2d_ms.mean": ("ms", 2.5),
    "dispatch_ms.mean": ("ms", 1.5),
    "account_ms.mean": ("ms", 1.0),
    "sync_ms.mean": ("ms", 0.5),
    "admit_ms.mean": ("ms", 0.75),
    "deliver_ms.mean": ("ms", 2.0),
    "open_session_ms.mean": ("ms", 2.5),
    "loop_wait_pct.steady": ("%", 12.5),
    "slow_steps.steady": ("count", 2.0),
}


def reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH, "metrics", name + ".py"))


def snapshot(ticks, steps, slow_steps, wait_s, sessions):
    n = ticks
    gw = {"gateway.launch": (n, 6e-3 * n, 0),
          "gateway.decide": (n, 0.25e-3 * n, 0),
          "gateway.stage_h2d": (n, 2.5e-3 * n, 0),
          "gateway.dispatch": (n, 1.5e-3 * n, 0),
          "gateway.account": (n, 1e-3 * n, 0),
          "gateway.sync": (n, 0.5e-3 * n, 0),
          "gateway.open_session": (sessions, 2.5e-3 * sessions, 0)}
    own = {"stream.step": (steps, 9e-3 * steps, slow_steps),
           "stream.admit": (steps, 0.75e-3 * steps, 0),
           "stream.deliver": (n, 2e-3 * n, 0),
           "stream.wait": (7, wait_s, 0)}
    return NS(phases=own, gateway=NS(phases=gw))


def stand_in(with_phases=True):
    w0 = snapshot(100, 110, 1, 3.0, 8)
    w1 = snapshot(1100, 1210, 3, 4.25, 8)
    if not with_phases:
        for st in (w0, w1):       # as a program without phase counters
            del st.phases, st.gateway.phases
    return NS(window=(50.0, 60.0), stats={"w0": w0, "w1": w1})


def test_benchmark_lists_every_reader_with_its_unit():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name, (unit, _) in READERS.items():
        assert per_layer[name]["unit"] == unit, name
        assert per_layer[name]["source"] in ("program_span",
                                             "program_counter")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_values(name):
    _, want = READERS[name]
    assert reader(name).read(stand_in()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", sorted(READERS))
def test_no_phases_read_none(name):
    assert reader(name).read(stand_in(with_phases=False)) is None


def test_readers_on_a_served_window():
    """Real ``StreamStats`` snapshots around a few served ticks."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    import numpy as np
    from repro.api import FrameRequest, StreamSplitGateway
    from repro.api.policies import FixedKPolicy
    from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder
    from repro.serving import SchedulerCfg, StreamServer
    cfg = AudioEncCfg(widths=(8, 8), strides=(1, 1), n_mels=8, frames=8,
                      d_embed=16, groups=2)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))
    gw = StreamSplitGateway(cfg, params, policy=FixedKPolicy(2, 1),
                            capacity=8, window=8, qos_reserve=0)
    srv = StreamServer(gw, cfg=SchedulerCfg(max_batch=4))
    sids = [srv.open_session().sid for _ in range(4)]
    rng = np.random.default_rng(0)

    def serve(t):
        for sid in sids:
            srv.submit(sid, FrameRequest(
                t=t, mel=rng.normal(size=(8, 8)).astype(np.float32)))
        srv.step()

    serve(0)
    w0 = srv.stats()
    for t in range(1, 4):
        serve(t)
    while srv.busy():
        srv.step()
    run = NS(window=(0.0, 1.0), stats={"w0": w0, "w1": srv.stats()})
    for name in READERS:
        v = reader(name).read(run)
        if name == "slow_steps.steady":
            assert v == 0.0
        else:
            assert v is not None and v >= 0.0, name
