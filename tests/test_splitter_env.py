"""Split engine exactness + calibrated env anchors + controller semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import Controller, run_episode
from repro.core.env import (EdgeCloudEnv, EnvCfg, battery_hours,
                            utility_to_accuracy)
from repro.core.splitter import SplitEngine
from repro.models.audio_encoder import AudioEncCfg, encode, init_audio_encoder


@pytest.fixture(scope="module")
def enc_setup():
    cfg = AudioEncCfg(widths=(16, 16, 32, 32), strides=(1, 2, 1, 2),
                      n_mels=32, frames=40, d_embed=32, groups=4)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_builds_executables_lazily(enc_setup):
    """A session that only ever uses one k compiles 2 callables, not
    2·(L+1) — this is what keeps gateway startup O(1) in L."""
    cfg, params = enc_setup
    eng = SplitEngine(cfg)
    assert not eng._edge and not eng._server
    mel = jax.random.normal(jax.random.PRNGKey(2), (1, cfg.frames,
                                                    cfg.n_mels))
    eng.run(params, mel, 2)
    assert set(eng._edge) == {2} and set(eng._server) == {2}
    eng.run(params, mel, cfg.n_blocks)       # k=L: edge-only executable
    assert set(eng._edge) == {2, cfg.n_blocks} and set(eng._server) == {2}


_L = 4   # enc_setup's n_blocks


@pytest.fixture(scope="module")
def engines(enc_setup):
    """One engine per wire format, shared so each (k, B) compiles once."""
    cfg, _ = enc_setup
    return {q: SplitEngine(cfg, quantize_wire=q) for q in (True, False)}


@pytest.mark.parametrize("quantize", [True, False])
@pytest.mark.parametrize("B", [1, 5, 16])
@pytest.mark.parametrize("k", [0, 1, _L // 2, _L - 1, _L])
def test_run_batch_async_bitwise_equals_three_launch_chain(
        enc_setup, engines, k, B, quantize):
    """The fused per-k executable serves the bits of the edge stage, the
    Pallas wire kernel and the server stage launched one by one, and the
    wire bytes ``run_batch`` reports.  Pinned on these inputs: inside the
    larger module XLA compiles the stages and the interpreted kernel anew,
    and on other inputs the two may differ in the last bit."""
    from repro.kernels import ops as kernel_ops
    cfg, params = enc_setup
    assert cfg.n_blocks == _L
    eng = engines[quantize]
    mel = jax.random.normal(jax.random.PRNGKey(10 * k + B),
                            (B, cfg.frames, cfg.n_mels))
    z, wire = eng.run_batch_async(params, mel, k)
    want = eng._edge_exec(k)(params, mel)
    if k < _L:
        if quantize:
            want = kernel_ops.wire_roundtrip(want)
        want = eng._server_exec(k)(params, want)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(want))
    assert wire == eng.run_batch(params, mel, k)[1]


def test_run_batch_async_launches_one_executable_every_k(enc_setup):
    """One launch per call at every k, counted by ``launches``; the
    per-stage server executables are never built on this path, while
    the ``run_batch`` reference still launches edge, wire and server."""
    cfg, params = enc_setup
    eng = SplitEngine(cfg)
    mel = jax.random.normal(jax.random.PRNGKey(3), (2, cfg.frames,
                                                    cfg.n_mels))
    for k in range(cfg.n_blocks + 1):
        n0 = eng.launches
        eng.run_batch_async(params, mel, k)
        assert eng.launches - n0 == 1, f"k={k}"
    assert set(eng._chain) == set(range(cfg.n_blocks + 1))
    assert not eng._server and set(eng._edge) == {cfg.n_blocks}
    for k, want in ((0, 3), (2, 3), (cfg.n_blocks, 1)):
        n0 = eng.launches
        eng.run_batch(params, mel, k)
        assert eng.launches - n0 == want, f"k={k}"


@pytest.mark.parametrize("method", ["run", "run_batch", "run_batch_async"])
def test_launches_counts_each_executable_called(enc_setup, method):
    """``launches`` moves by exactly the number of compiled executables
    the call invoked, counted by spies on every cached executable."""
    cfg, params = enc_setup
    eng = SplitEngine(cfg)
    mel = jax.random.normal(jax.random.PRNGKey(4), (2, cfg.frames,
                                                    cfg.n_mels))
    called = []

    def spy(exe, name):
        if getattr(exe, "spied", False):
            return exe
        def wrapped(*args):
            called.append(name)
            return exe(*args)
        wrapped.spied = True
        return wrapped

    run = getattr(eng, method)
    eng._qdq_tensor = spy(eng._qdq_tensor, "qdq")
    eng._qdq_sample = spy(eng._qdq_sample, "qdq")
    for k in (0, _L // 2, _L):
        run(params, mel, k)                  # build this k's executables
        for cache in (eng._edge, eng._server, eng._chain):
            for kk in list(cache):
                cache[kk] = spy(cache[kk], kk)
        called.clear()
        n0 = eng.launches
        run(params, mel, k)
        assert called and eng.launches - n0 == len(called), (k, called)


def test_split_exact_every_k_fp32(enc_setup):
    cfg, params = enc_setup
    eng = SplitEngine(cfg, quantize_wire=False)
    mel = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.frames, cfg.n_mels))
    full = eng.full(params, mel)
    for k in range(cfg.n_blocks + 1):
        z, wire = eng.run(params, mel, k)
        np.testing.assert_allclose(np.asarray(z), np.asarray(full),
                                   atol=1e-5, err_msg=f"k={k}")
        if k < cfg.n_blocks:
            assert wire > 0


def test_split_int8_wire_small_error(enc_setup):
    cfg, params = enc_setup
    eng = SplitEngine(cfg, quantize_wire=True)
    mel = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.frames, cfg.n_mels))
    full = eng.full(params, mel)
    for k in range(1, cfg.n_blocks):
        z, wire_q = eng.run(params, mel, k)
        cos = float(jnp.sum(z * full, -1).mean())
        assert cos > 0.999, f"k={k} cos={cos}"
        # int8 wire is ~4x smaller
        z2, wire_f = SplitEngine(cfg, quantize_wire=False).run(params, mel, k)
        assert wire_q < wire_f / 3.5


def test_env_calibration_anchors():
    """Table 2 anchors: server-only 187.2 mJ / 5.3 h; edge-only 67.4 mJ."""
    env = EdgeCloudEnv(EnvCfg(net="stable", horizon=400))
    s_srv = run_episode(env, Controller("server", env.L), seed=3)
    assert abs(s_srv["energy_mj"] - 187.2) / 187.2 < 0.05
    assert abs(battery_hours(s_srv["energy_mj"]) - 5.3) < 0.5
    assert abs(s_srv["kb_per_batch"] - 256.0) / 256.0 < 0.05

    env = EdgeCloudEnv(EnvCfg(net="stable", horizon=400))
    s_edge = run_episode(env, Controller("edge", env.L), seed=3)
    assert abs(s_edge["energy_mj"] - 67.4) / 67.4 < 0.08
    # accuracy ordering (Fig. 8): server > static-offload > edge-only
    acc_srv = utility_to_accuracy(s_srv["utility"])
    acc_edge = utility_to_accuracy(s_edge["utility"])
    assert acc_srv > 72.0 and acc_edge < 62.0


def test_static_split_degrades_under_congestion():
    """§1: static split suffers under volatility via latency timeouts."""
    stable = EdgeCloudEnv(EnvCfg(net="stable", horizon=400))
    s1 = run_episode(stable, Controller("static", stable.L, static_k=3),
                     seed=5)
    cong = EdgeCloudEnv(EnvCfg(net="congested", horizon=400))
    s2 = run_episode(cong, Controller("static", cong.L, static_k=3), seed=5)
    assert s2["drop_rate"] > 0.15 > s1["drop_rate"]
    assert utility_to_accuracy(s2["utility"]) < \
        utility_to_accuracy(s1["utility"]) - 3.0


def test_rule_policy_adapts_but_slower():
    """Rule-based backs off under congestion (no catastrophic drops)."""
    cong = EdgeCloudEnv(EnvCfg(net="congested", horizon=400))
    s = run_episode(cong, Controller("rule", cong.L), seed=5)
    assert s["drop_rate"] < 0.2


def test_controller_atomic_transitions():
    env = EdgeCloudEnv(EnvCfg(net="variable", horizon=50))
    c = Controller("rule", env.L)
    obs = env.reset(seed=0)
    ks = []
    for _ in range(50):
        k = c.decide(obs)
        ks.append(k)
        obs, _, done, _ = env.step(k)
    # decisions are per-interval constants (atomicity is structural here):
    # the controller only ever returns the k applied to the *next* block
    assert c.transitions == sum(1 for a, b in zip(ks, ks[1:]) if a != b) + \
        (1 if ks and ks[0] != env.L else 0)
