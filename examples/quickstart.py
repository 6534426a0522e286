"""Quickstart: the StreamSplit public API in ~60 lines.

One typed surface runs the whole pipeline — open a session on the
gateway, submit frames, tick: uncertainty-driven split placement,
k-bucketed batched dispatch, INT8 wire accounting, temporal-buffer
ingest, hybrid-loss refinement and lazy sync all happen behind
``StreamSplitGateway``.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.api import FrameRequest, QoSClass, StreamSplitGateway, make_policy
from repro.launch.cache import enable_compile_cache
from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder

enable_compile_cache()

# A smoke-scale encoder (the paper's model family, CPU-friendly widths).
CFG = AudioEncCfg(widths=(16, 16, 32, 32), strides=(1, 2, 1, 2),
                  n_mels=32, frames=40, d_embed=32, groups=4)
N_CLASSES = 4


def head_init(key):
    return {"w": 0.01 * jax.random.normal(key, (CFG.d_embed, N_CLASSES))}


def head_apply(p, z):
    return z @ p["w"]


params = init_audio_encoder(CFG, jax.random.PRNGKey(0))

# 1. The gateway IS the pipeline: an entropy policy (the cascade's routing
#    as a SplitPolicy) + a fleet buffer + a refiner + lazy sync in one box.
gw = StreamSplitGateway(
    CFG, params,
    policy=make_policy("entropy", CFG.n_blocks, threshold=0.6, offload_k=2),
    capacity=8, window=32, head_init=head_init, head_apply=head_apply,
    refine_every=4)

# 2. Sessions are typed and QoS-classed.
info = gw.open_session(platform="pi4", qos=QoSClass.INTERACTIVE)
print(f"session {info.sid} open ({info.platform}, {info.qos.value})")

# 3. Stream frames: easy (low-U) frames stay on the edge, hard ones split.
rng = np.random.default_rng(0)
for t in range(12):
    u = 0.2 if t % 3 else 0.9          # every third frame is "hard"
    mel = rng.normal(size=(CFG.frames, CFG.n_mels)).astype(np.float32)
    gw.submit(info.sid, FrameRequest(t=t, mel=mel, label=t % N_CLASSES,
                                     u=u, cpu=0.3, bandwidth_mbps=20.0))
    (r,) = gw.tick()
    print(f"frame {t}: U={u:.1f} -> route={r.route:6s} k={r.k} "
          f"wire={r.wire_bytes:5d} B  z[:3]={np.round(r.z[:3], 3)}")

# 4. One scoreboard for the whole serving plane.
s = gw.stats()
print(f"\n{s.frames} frames in {s.dispatches} dispatches "
      f"({s.frames_per_dispatch:.1f} frames/dispatch), "
      f"routed={s.routed}, wire={s.wire_bytes / 1024:.1f} KB, "
      f"refine rounds={s.refine_rounds} (last loss {s.last_refine_loss:.3f}), "
      f"lazy sync={s.sync_bytes / 1024:.0f} KB")
final = gw.close_session(info.sid)
print(f"closed session {final.sid}: {final.frames} frames, "
      f"{final.transitions} atomic split transitions, "
      f"buffer fill {final.fill_fraction:.2f}")
