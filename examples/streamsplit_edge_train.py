"""End-to-end driver: continuous StreamSplit training on a synthetic
ambient-audio stream, then serving the trained encoder through the
typed gateway API — the paper's full loop at CPU scale.

Part 1 trains the representation (edge learner + GMM virtual negatives +
hybrid server loss).  Part 2 serves the trained weights through
``StreamSplitGateway``: the policy decides placement per frame, frames
ride k-bucketed dispatches, the split link is INT8-accounted and lazy
sync runs behind the same surface, while the calibrated edge-cloud
simulator prices each placement (latency/energy/drops).  Part 3 compares
against a server-only gateway.

    PYTHONPATH=src python examples/streamsplit_edge_train.py --steps 300
"""
import argparse
import os
import sys

import jax
import numpy as np

# benchmarks/ lives at the repo root, not under src/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.edge_train import ENC, retrieval_metrics, train_representation
from repro.api import FrameRequest, StreamSplitGateway, make_policy
from repro.core.env import EdgeCloudEnv, EnvCfg, utility_to_accuracy
from repro.data.audio_stream import AudioStream, StreamCfg


def serve_stream(policy_kind, params, mels, ys, *, net="variable", seed=0):
    """Serve the stream through one gateway session; returns the env
    summary (deployment costs) + gateway stats (measured pipeline)."""
    env = EdgeCloudEnv(EnvCfg(enc=ENC, net=net, horizon=len(mels)))
    gw = StreamSplitGateway(ENC, params,
                            policy=make_policy(policy_kind, env.L),
                            capacity=2, window=100, qos_reserve=0)
    sid = gw.open_session(platform="pi4").sid
    obs = env.reset(seed=seed)
    done, t, drops = False, 0, 0
    while not done:
        gw.submit(sid, FrameRequest(
            t=t, mel=mels[t], label=int(ys[t]), u=float(obs[0]),
            cpu=float(obs[1]), bandwidth_mbps=env.bw))
        (r,) = gw.tick()
        # the decision prices the NEXT block in the simulator — the same
        # atomic-transition boundary the controller semantics define
        obs, _, done, info = env.step(r.k)
        drops += int(info["dropped"])
        t += 1
    info_s = gw.close_session(sid)
    return env.summary(), gw.stats(), info_s, drops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--frames", type=int, default=300,
                    help="frames to serve through the gateway")
    ap.add_argument("--policy", default="rule",
                    choices=["rule", "static", "edge", "server", "entropy"])
    args = ap.parse_args()

    # 1. representation learning (the Edge Learner + Server Refiner loop)
    print(f"[1/3] training StreamSplit representation for {args.steps} "
          f"steps on the synthetic stream ...")
    res = train_representation("streamsplit", steps=args.steps, eval_n=240)
    mAP, r1 = retrieval_metrics(res.eval_z, res.eval_y)
    print(f"      linear probe {100*res.probe_acc:.1f}%  "
          f"mAP@10 {mAP:.3f}  R@1 {100*r1:.1f}%  "
          f"(collapse |cos| {res.collapse:.2f})")

    # 2. serve the trained encoder through the gateway over a volatile link
    print(f"[2/3] serving {args.frames} frames through the gateway "
          f"({args.policy} policy, variable network)")
    stream = AudioStream(StreamCfg(seed=1))
    mels, ys, _ = stream.batch(args.frames)
    mels = np.asarray(mels[:, :ENC.frames], np.float32)
    s, st, info, drops = serve_stream(args.policy, res.params, mels, ys)
    print(f"      {s['lat_ms']*8:6.0f} ms/batch   "
          f"{s['kb_per_batch']:6.1f} KB/batch   "
          f"{s['energy_mj']:5.1f} mJ/frame   drops {drops/max(st.frames,1):.2%}")
    print(f"      gateway: {st.frames} frames, routed={st.routed}, "
          f"split-link {st.wire_bytes/1024:.0f} KB measured, "
          f"{info.transitions} atomic transitions, "
          f"lazy sync {st.sync_bytes/1024:.0f} KB downlink")

    # 3. headline vs the server-centric baseline, same API surface
    print("[3/3] system summary (vs server-only gateway)")
    s2, st2, _, _ = serve_stream("server", res.params, mels, ys)
    print(f"      bandwidth {100*(1 - s['kb_per_batch']/s2['kb_per_batch']):.1f}% lower   "
          f"energy {100*(1 - s['energy_mj']/s2['energy_mj']):.1f}% lower   "
          f"accuracy {utility_to_accuracy(s['utility']):.1f}% vs "
          f"{utility_to_accuracy(s2['utility']):.1f}%")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
