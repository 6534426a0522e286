#!/usr/bin/env python3
"""Serve the published-width StreamSplit encoder on a TPU, end to end.

One process.  With no arguments it uses one chip: ``AudioEncCfg()`` at
its defaults (128 mels x 100 frames, widths 64->512 over 8 splittable
blocks, d=128) behind ``StreamServer`` -> ``StreamSplitGateway`` ->
``ShardedFleetBackend`` on a one-device sessions mesh, with 4096 session
rings (W=100, d=128: 210 MB on the device), a task head and a GMM memory
of C=64 components.  Client threads in all three QoS classes stream a
few hundred frames; a bandwidth-tier split policy spreads them over k=0,
an interior boundary and k=L, so the compiled Pallas wire kernel and
several edge/server executables run, and the gateway refines the fleet
every few ticks.

The run fails (non-zero exit) when any of these does not hold:

- conservation, per QoS class: submitted == served + depth + in_flight
  + shed_expired, and preempted == requeued;
- at least two refine rounds ran, and every refine loss is finite;
- every served embedding is within ``TOL`` of a plain float32 reference
  (``encode()`` per frame under ``default_matmul_precision("highest")``
  with the same per-sample int8 round-trip at the same k, in jnp);
- the split engine's one executable at an interior k, lowered, holds
  ``tpu_custom_call``: the wire kernel is Mosaic, not the Pallas
  interpreter;
- at k=0 and at an interior k, that one executable (what
  ``run_batch_async`` launches) equals the edge stage, the wire kernel
  and the server stage launched one by one: bitwise on the TPU, within
  ``TOL`` on the CPU, where the interpreted kernel may round otherwise.

Whether served embeddings are bitwise equal to per-frame
``SplitEngine.run`` is printed, not asserted.

``--chips 4`` runs only the sharded phase, on four chips: a
``ShardedFleetBackend`` over all four with ``shard_dispatch`` against the
1-shard plane on the same frames.  Embeddings agree within ``TOL``, every
shard serves frames on the device that holds its rings, and the sharded
refine loss matches the unsharded estimator on the same rings within
``REFINE_RTOL``.

    python chip_smoke.py [--chips 4]

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``,
printed only when every check passed.  Without a TPU the script exits
non-zero before serving anything.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Served-vs-reference bound on the l2-normalized embedding (max abs error
# of any element; typical elements are ~0.07).  The served path runs at
# the TPU's default precision, where an f32 convolution multiplies in one
# bf16 pass (unit roundoff 2^-9) over 17 convolutions, and an int8 bin a
# rounding flips moves that element by one quantization step.  Emulating
# one bf16 pass with f32 accumulation on the CPU gave at most 2.2e-3 over
# 256 frames at k in {0, 2, 5, 8}; 1e-2 leaves a 4.5x margin and still
# fails a wrong split, wrong weights or a broken kernel (errors ~0.1).
TOL = 1e-2
# Sharded vs unsharded refine loss: the per-session terms are the same
# arithmetic; only the cross-shard psum/pmean re-associates the mean over
# sessions and the gradient sum (float32, a few ulps per level).
REFINE_RTOL = 1e-5

CAPACITY = 4096          # session rings on the chip (1024 per chip on 4)
WINDOW = 100             # frames per ring: 100 x 1 s windows
N_COMPONENTS = 64        # GMM memory, paper section 5
N_CLASSES = 8            # task-head classes of the labelled frames
OPEN_FRACTION = 0.75     # of CAPACITY open: streaming + quiet sessions
CLIENTS = {"interactive": 8, "standard": 16, "bulk": 24}
FRAMES_PER_CLIENT = 8    # 48 clients x 8 = 384 frames
MAX_BATCH = 32           # frames per tick
REFINE_EVERY = 4         # ticks: 384 / 32 >= 12 ticks -> >= 3 rounds
SEED = 0


def fail(msg):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg):
    if not cond:
        fail(msg)


def tpu_devices(n):
    """The first ``n`` TPU devices; exits non-zero on anything else."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{d.platform!r}); nothing was served")
    if len(devs) < n:
        raise SystemExit(f"chip_smoke: {n} chips asked for, {len(devs)} "
                         "found")
    return devs[:n]


class CompileClock:
    """Sums the XLA backend compile time JAX reports for this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


class BandwidthTierPolicy:
    """A ``SplitPolicy``: the slower a client's link, the deeper the edge
    prefix — k=L (the embedding alone crosses) below a third of the
    normalized bandwidth, k=L/2 in the middle third, k=0 (the mel
    crosses) above."""

    def __init__(self, L):
        import numpy as np
        self.L = L
        self.ks = np.array([L, L // 2, 0], np.int64)
        self.edges = np.array([1 / 3, 2 / 3], np.float32)

    def decide(self, obs_batch):
        import numpy as np
        bw = np.asarray(obs_batch, np.float32)[:, 2]
        return self.ks[np.searchsorted(self.edges, bw, side="right")]


def make_backend(cfg, mesh, *, capacity):
    """A ``ShardedFleetBackend`` that keeps every refine loss it returns."""
    import jax
    from repro.core.fleet_backend import ShardedFleetBackend

    class Backend(ShardedFleetBackend):
        def refine(self, key):
            out = super().refine(key)
            self.losses.append(out[0])
            return out

    def head_init(key):
        return {"w": 0.01 * jax.random.normal(key, (cfg.d_embed, N_CLASSES))}

    def head_apply(p, z):
        return z @ p["w"]

    b = Backend(capacity=capacity, window=WINDOW, dim=cfg.d_embed,
                head_init=head_init, head_apply=head_apply,
                n_components=N_COMPONENTS, mesh=mesh, seed=SEED)
    b.losses = []
    return b


def make_clients(cfg, clients, per_client):
    """Per client: its QoS class and its frames, labelled class templates
    plus noise, each with a uniform uncertainty and link bandwidth."""
    import numpy as np
    from repro.api import FrameRequest, QoSClass
    rng = np.random.default_rng(SEED)
    templates = rng.normal(size=(N_CLASSES, cfg.frames, cfg.n_mels))
    out = []
    for qos, n in clients.items():
        for _ in range(n):
            frames = []
            for t in range(per_client):
                lab = int(rng.integers(N_CLASSES))
                mel = templates[lab] + 0.1 * rng.normal(size=templates[0].shape)
                frames.append(FrameRequest(
                    t=t, mel=mel.astype(np.float32), label=lab,
                    u=float(rng.uniform()),
                    bandwidth_mbps=float(rng.uniform(1.0, 50.0))))
            out.append((QoSClass(qos), frames))
    return out


def open_quiet(open_session, capacity, n_open):
    """Fill the fleet to ``OPEN_FRACTION`` with sessions that stream
    nothing during the run (connected clients between windows)."""
    from repro.api import QoSClass
    for _ in range(int(capacity * OPEN_FRACTION) - n_open):
        open_session(qos=QoSClass.STANDARD)


def warm(engine, params, cfg, ks, max_batch):
    """Compile every (k, pow2 bucket) chain the ticks can launch."""
    import jax
    import jax.numpy as jnp
    b = 1
    while b <= max_batch:
        mel = jnp.zeros((b, cfg.frames, cfg.n_mels), jnp.float32)
        for k in ks:
            jax.block_until_ready(engine.run_batch_async(params, mel, k)[0])
        b *= 2


def reference(cfg, params, mels, ks):
    """The plain float32 reference: ``encode()`` per frame at the highest
    matmul precision, with the int8 wire round-trip at the frame's k done
    per sample in jnp (``quant.int8``)."""
    import jax
    import numpy as np
    from repro.models import audio_encoder as enc
    from repro.quant.int8 import dequantize, quantize

    def fn(k, p, mel):
        if k == cfg.n_blocks:
            return enc.encode(cfg, p, mel)
        act = mel if k == 0 else enc.encode(cfg, p, mel, end=k)
        return enc.encode(cfg, p, dequantize(quantize(act)), start=k)

    jitted = {}
    out = []
    with jax.default_matmul_precision("highest"):
        for mel, k in zip(mels, ks):
            if k not in jitted:
                jitted[k] = jax.jit(lambda p, m, k=k: fn(k, p, m))
            out.append(np.asarray(jitted[k](params, mel[None]))[0])
    return np.stack(out)


def compare(name, got, want):
    """-> max abs error; fails past ``TOL`` or on a non-finite value."""
    import numpy as np
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
          f"{want.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite embedding")
    err = float(np.abs(got - want).max())
    cos = float((got * want).sum(-1).min())
    print(f"{name}: max abs error {err!r} (tolerance {TOL}), "
          f"min cosine {cos!r}", flush=True)
    check(err <= TOL, f"{name}: max abs error {err} > {TOL}")
    return err


def wire_is_compiled(engine, cfg, params):
    """Lower the split engine's one executable at an interior boundary
    as ``run_batch_async`` launches it (edge stage, wire kernel, server
    stage); its text must hold the wire kernel's Mosaic custom call."""
    import jax
    import jax.numpy as jnp
    mel = jax.ShapeDtypeStruct((MAX_BATCH, cfg.frames, cfg.n_mels),
                               jnp.float32)
    k = cfg.n_blocks // 2
    text = engine._chain_exec(k).lower(params, mel).as_text()
    ok = "tpu_custom_call" in text
    print(f"split chain at k={k} {mel.shape}: lowered text holds "
          f"tpu_custom_call: {ok}", flush=True)
    check(ok, "the wire stage lowered without tpu_custom_call "
          "(Pallas interpreter on the chip)")


def fused_vs_stages(engine, cfg, params, max_batch):
    """``run_batch_async``'s one executable against the edge stage, the
    wire kernel and the server stage launched one by one, on the same
    ``max_batch`` frames at k=0 and at an interior k.  Fails past
    ``TOL``, and on the TPU on any bit; -> (frames bitwise equal, max
    abs difference)."""
    import jax
    import numpy as np
    from repro.kernels import ops
    mel = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                            (max_batch, cfg.frames, cfg.n_mels))
    equal, worst = 0, 0.0
    for k in (0, cfg.n_blocks // 2):
        fused = np.asarray(engine.run_batch_async(params, mel, k)[0])
        act = mel if k == 0 else engine._edge_exec(k)(params, mel)
        apart = np.asarray(engine._server_exec(k)(params,
                                                  ops.wire_roundtrip(act)))
        same = int((fused == apart).all(axis=1).sum())
        diff = float(np.abs(fused - apart).max())
        print(f"one executable vs three launches at k={k}: {same} of "
              f"{max_batch} frames bitwise equal (max abs difference "
              f"{diff!r})", flush=True)
        check(diff <= TOL, f"the fused split chain at k={k} is {diff} off "
              "its three stages launched one by one")
        # the Mosaic kernel is sealed inside the fused module, and on the
        # chip the two paths have matched bitwise; the CPU's interpreted
        # kernel compiles anew there and may differ in the last bit
        check(jax.default_backend() != "tpu" or same == max_batch,
              f"the fused split chain at k={k} is not bitwise equal to its "
              "three stages launched one by one on the TPU")
        equal, worst = equal + same, max(worst, diff)
    return equal, worst


def serve_one_chip(devices, cfg, *, capacity=CAPACITY, clients=CLIENTS,
                   per_client=FRAMES_PER_CLIENT, max_batch=MAX_BATCH):
    """The main path on one device; -> a summary dict of what it saw."""
    import jax
    import numpy as np
    from repro.api import StreamSplitGateway
    from repro.launch.mesh import make_sessions_mesh
    from repro.models.audio_encoder import init_audio_encoder
    from repro.serving import QueueFullError, SchedulerCfg, StreamServer

    clock = CompileClock()
    t0 = time.perf_counter()
    params = init_audio_encoder(cfg, jax.random.PRNGKey(SEED))
    check(len(devices) == 1, "the one-chip phase takes one device")
    backend = make_backend(cfg, make_sessions_mesh(1), capacity=capacity)
    policy = BandwidthTierPolicy(cfg.n_blocks)
    gw = StreamSplitGateway(cfg, params, policy=policy, backend=backend,
                            refine_every=REFINE_EVERY)
    results = []
    server = StreamServer(gw, cfg=SchedulerCfg(max_batch=max_batch),
                          queue_maxlen=4 * max_batch,
                          on_result=results.append)
    warm(gw.engine, params, cfg, policy.ks.tolist(), max_batch)
    warm_s = time.perf_counter() - t0
    print(f"set-up: {warm_s:.3f} s to build the fleet and compile "
          f"{len(policy.ks)} split points x pow2 buckets <= {max_batch}",
          flush=True)
    wire_is_compiled(gw.engine, cfg, params)
    fused_equal, fused_diff = fused_vs_stages(gw.engine, cfg, params,
                                              max_batch)

    population = make_clients(cfg, clients, per_client)
    errors = []

    def client(sid, qos, frames):
        try:
            for frame in frames:
                while True:
                    try:
                        server.submit(sid, frame)
                        break
                    except QueueFullError:    # bounded queue: back off
                        time.sleep(1e-3)
                if qos.value == "interactive":
                    time.sleep(2e-3)          # paced like a live mic
            server.close_session(sid)
        except Exception as e:                # surfaced after the join
            errors.append(e)

    t_serve = time.perf_counter()
    with server:
        sids = [server.open_session(qos=qos).sid for qos, _ in population]
        open_quiet(server.open_session, capacity, len(sids))
        n_open = gw.stats().sessions_open
        mels = {(sid, f.t): f.mel for sid, (_, frames) in zip(sids, population)
                for f in frames}
        threads = [threading.Thread(target=client, args=(sid, qos, frames))
                   for sid, (qos, frames) in zip(sids, population)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
            check(not th.is_alive(), "a client thread did not finish")
    serve_s = time.perf_counter() - t_serve
    check(not errors, f"client errors: {errors!r}")

    st = server.stats()
    n_sent = len(mels)
    print(f"served {sum(st.frames_served.values())} of {n_sent} frames in "
          f"{st.ticks} ticks ({st.pipelined_ticks} pipelined) over "
          f"{serve_s:.3f} s with {n_open} sessions open of {capacity}",
          flush=True)
    for cls in ("interactive", "standard", "bulk"):
        w = st.queue_wait_ms[cls]
        print(f"  {cls:>11}: submitted {st.frames_submitted[cls]} served "
              f"{st.frames_served[cls]} depth {st.queue_depth[cls]} "
              f"in_flight {st.in_flight[cls]} shed {st.shed_expired[cls]} "
              f"preempted {st.preempted[cls]} requeued {st.requeued[cls]} "
              f"| wait p95 {w['p95']!r} ms", flush=True)
        check(st.frames_submitted[cls] == st.frames_served[cls]
              + st.queue_depth[cls] + st.in_flight[cls]
              + st.shed_expired[cls], f"{cls}: conservation broken")
        check(st.preempted[cls] == st.requeued[cls],
              f"{cls}: preempted != requeued")
    check(sum(st.frames_served.values()) == n_sent == len(results),
          "not every frame was served")

    losses = backend.losses
    print(f"refine: {len(losses)} rounds, losses {losses!r}", flush=True)
    check(len(losses) >= 2, f"{len(losses)} refine rounds, want >= 2")
    check(all(math.isfinite(x) for x in losses), "non-finite refine loss")

    ks = np.array([r.k for r in results])
    by_k = {int(k): int((ks == k).sum()) for k in np.unique(ks)}
    print(f"frames by split point k: {by_k}", flush=True)
    check({0, cfg.n_blocks} <= set(by_k) and len(by_k) >= 3,
          "the policy did not mix k=0, an interior split and k=L")

    served = np.stack([r.z for r in results])
    frame_mels = [mels[(r.sid, r.t)] for r in results]
    err = compare("served vs float32 reference", served,
                  reference(cfg, params, frame_mels, ks))
    per_frame = np.stack([
        np.asarray(gw.engine.run(params, m[None], int(k))[0])[0]
        for m, k in zip(frame_mels, ks)])
    same = int((per_frame == served).all(axis=1).sum())
    diff = float(np.abs(per_frame - served).max())
    print(f"bitwise equal to per-frame SplitEngine.run: {same} of "
          f"{len(served)} frames (max abs difference {diff!r})", flush=True)
    print(f"compile: {clock.seconds:.3f} s in {clock.count} XLA compiles "
          "(set-up and first use; no timed window)", flush=True)
    return {"max_abs_error": err, "bitwise_equal": same,
            "frames": len(served), "refine_losses": losses,
            "fused_bitwise_equal": fused_equal, "fused_max_abs": fused_diff}


def serve_sharded(devices, cfg, *, capacity=CAPACITY, clients=CLIENTS,
                  per_client=FRAMES_PER_CLIENT, max_batch=MAX_BATCH):
    """The sharded fleet over ``devices`` against the 1-shard plane, on
    the same frames in the same ticks."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.api import StreamSplitGateway
    from repro.core.fleet_refiner import FleetRefinerState
    from repro.distributed.sharding import sessions_sharding
    from repro.launch.mesh import make_sessions_mesh

    from repro.models.audio_encoder import init_audio_encoder
    clock = CompileClock()
    S = len(devices)
    params = init_audio_encoder(cfg, jax.random.PRNGKey(SEED))
    policy = BandwidthTierPolicy(cfg.n_blocks)
    planes = {}
    for name, shards in (("sharded", S), ("one-shard", 1)):
        backend = make_backend(cfg, make_sessions_mesh(shards),
                               capacity=capacity)
        planes[name] = StreamSplitGateway(cfg, params, policy=policy,
                                          backend=backend,
                                          refine_every=REFINE_EVERY)
    gw_s, gw_1 = planes["sharded"], planes["one-shard"]
    check(gw_s.shard_dispatch and gw_s.backend.shards == S,
          "the sharded plane is not dispatching over every chip")
    check(not gw_1.shard_dispatch, "the reference plane is sharded")

    population = make_clients(cfg, clients, per_client)
    sids = {name: [gw.open_session(qos=qos).sid for qos, _ in population]
            for name, gw in planes.items()}
    for gw in planes.values():
        open_quiet(gw.open_session, capacity, len(population))
    # round-robin over clients, MAX_BATCH frames per tick, both planes
    order = [(i, frames[t]) for t in range(per_client)
             for i, (_, frames) in enumerate(population)]
    z = {name: [] for name in planes}
    for lo in range(0, len(order), max_batch):
        for name, gw in planes.items():
            for i, frame in order[lo:lo + max_batch]:
                gw.submit(sids[name][i], frame)
            z[name].extend(r.z for r in gw.tick())
    got, want = np.stack(z["sharded"]), np.stack(z["one-shard"])
    err = compare(f"{S}-shard vs 1-shard plane", got, want)
    same = int((got == want).all(axis=1).sum())
    print(f"bitwise equal across planes: {same} of {len(got)} frames",
          flush=True)

    st = gw_s.stats()
    print(f"per-shard frames: dispatched {st.dispatch_shard_frames}, "
          f"ingested {st.shard_frames}", flush=True)
    check(st.dispatch_shard_frames == st.shard_frames,
          "frames dispatched on a shard other than their ring's")
    check(all(n > 0 for n in st.dispatch_shard_frames),
          "a shard served no frames")
    ring_devices = {sh.device for sh in gw_s.backend.z.addressable_shards}
    check(ring_devices == set(devices), "the rings do not span every chip")
    for name, gw in planes.items():
        print(f"{name} refine losses (own rings): {gw.backend.losses!r}",
              flush=True)
        check(len(gw.backend.losses) >= 2
              and all(math.isfinite(x) for x in gw.backend.losses),
              f"{name}: fewer than 2 or non-finite refine losses")

    # the estimator: an unsharded twin on a copy of the sharded rings,
    # head, optimizer and memory; both take the same refine steps
    b_s = gw_s.backend
    twin = make_backend(cfg, make_sessions_mesh(1), capacity=capacity)
    rows = sessions_sharding(twin.mesh, twin.axis)
    twin.z, twin.t, twin.label, twin.newest, twin.active_dev = (
        jax.device_put(a, rows)
        for a in (b_s.z, b_s.t, b_s.label, b_s.newest, b_s.active_dev))
    rep = NamedSharding(twin.mesh, P())
    st_s = b_s.refiner.state
    twin.refiner.state = FleetRefinerState(
        jax.device_put(st_s.params, rep), jax.device_put(st_s.opt_state, rep),
        st_s.step)
    twin.memory = jax.device_put(b_s.memory, rep)
    worst = 0.0
    for r in range(2):
        key = jax.random.PRNGKey(1000 + r)
        l_s, l_1 = b_s.refine(key)[0], twin.refine(key)[0]
        rel = abs(l_s - l_1) / max(abs(l_1), 1e-30)
        worst = max(worst, rel)
        print(f"refine round {r}: {S}-shard loss {l_s!r}, unsharded "
              f"{l_1!r}, relative difference {rel!r}", flush=True)
        check(rel <= REFINE_RTOL, f"sharded refine loss off the unsharded "
              f"estimator by {rel} > {REFINE_RTOL}")
    print(f"compile: {clock.seconds:.3f} s in {clock.count} XLA compiles",
          flush=True)
    return {"max_abs_error": err, "bitwise_equal": same,
            "shard_frames": st.dispatch_shard_frames,
            "refine_rel_diff": worst}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving main path on one chip (default); "
                         "4: only the sharded fleet over four chips "
                         "against the 1-shard plane")
    args = ap.parse_args(argv)
    # libtpu logs under /tmp unless told otherwise; keep the run inside
    # its checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = tpu_devices(args.chips)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.cache import enable_compile_cache
    from repro.models.audio_encoder import AudioEncCfg
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 1:
        serve_one_chip(devices, AudioEncCfg())
    else:
        serve_sharded(devices, AudioEncCfg())
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
