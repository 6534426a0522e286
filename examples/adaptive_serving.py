"""Uncertainty-routed adaptive serving through the gateway: the paper's
offload policy as a serving pattern — easy (low GMM-entropy) frames stay
fully local on the edge tier, hard frames escalate so the server runs the
deep suffix of the stack.

The ``entropy`` ``SplitPolicy`` is the cascade's threshold routing behind
the unified API: every tick the escalated frames share ONE padded split
dispatch and the local frames share another (the gateway analogue of
``CascadeServer.handle``'s two sub-batches).

NOTE: the hand-rolled ``submit``/``tick`` loop below is the *diagnostic*
way to drive the pipeline (here it runs ``tick(profile=True)`` to
attribute per-tier latency).  To actually serve a fleet, use the
always-on streaming runtime instead — ``examples/streaming_demo.py`` is
the canonical entry point (``repro.serving.StreamServer``: threaded
ingest, QoS scheduling, cross-tick pipelining; docs/STREAMING.md).

    PYTHONPATH=src python examples/adaptive_serving.py
"""
import jax
import numpy as np

from repro.api import FrameRequest, StreamSplitGateway, make_policy
from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder

CFG = AudioEncCfg(widths=(16, 16, 32, 32), strides=(1, 2, 1, 2),
                  n_mels=32, frames=40, d_embed=32, groups=4)
N_SESSIONS = 16
N_TICKS = 10
THRESHOLD = 0.7           # paper §6.5.2: offload when U_t > 0.7


def main():
    params = init_audio_encoder(CFG, jax.random.PRNGKey(0))
    gw = StreamSplitGateway(
        CFG, params,
        policy=make_policy("entropy", CFG.n_blocks, threshold=THRESHOLD,
                           offload_k=2),
        capacity=N_SESSIONS, window=32, qos_reserve=0)
    sids = [gw.open_session().sid for _ in range(N_SESSIONS)]
    rng = np.random.default_rng(0)

    lat = {"edge": [], "split": []}
    for t in range(N_TICKS):
        for sid in sids:
            # bimodal uncertainty: mostly calm background, occasional
            # transients (the EcoStream-Wild regime mix)
            u = rng.uniform(0.75, 1.0) if rng.random() < 0.25 \
                else rng.uniform(0.05, 0.5)
            mel = rng.normal(size=(CFG.frames, CFG.n_mels)).astype(np.float32)
            gw.submit(sid, FrameRequest(t=t, mel=mel, u=float(u),
                                        bandwidth_mbps=20.0))
        # profile=True: per-bucket timing (one sync per bucket) so the
        # two tiers are attributable — the serving default is the
        # overlapped single-sync tick, whose latency_ms is a per-TICK
        # figure identical across routes (docs/PERF.md)
        for r in gw.tick(profile=True):
            if t > 0:          # steady state: tick 0 pays the JIT compile
                lat[r.route].append(r.latency_ms)

    s = gw.stats()
    esc = s.routed["split"] / max(s.frames, 1)
    print(f"served {s.frames} frames over {s.ticks} ticks in "
          f"{s.dispatches} dispatches ({s.frames_per_dispatch:.1f} "
          f"frames/dispatch)")
    print(f"escalation rate {esc:.2f} (threshold U>{THRESHOLD}) | "
          f"edge tier {np.median(lat['edge']):.2f} ms/frame | "
          f"escalated tier {np.median(lat['split']):.2f} ms/frame "
          f"(median, profile mode: amortized over each bucket)")
    print(f"split-link traffic {s.wire_bytes/1024:.1f} KB — "
          f"{100*(1-esc):.0f}% of frames never ship an activation")
    for sid in sids:
        gw.close_session(sid)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
