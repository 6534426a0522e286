"""Fleet serving benchmark: batched multi-session refinement, the
host-vs-device-resident data plane, and cascade serving throughput vs.
fleet size.

Measures, for N ∈ {1, 8, 32, 128} concurrent sessions:

- refine-steps/sec — one vmapped ``FleetRefiner.refine`` over the packed
  ``(N, W, d)`` fleet vs. N sequential ``ServerRefiner.refine`` calls
  (the seed's serving model: one dispatch per session);
- backend rounds/sec — one serving round (batched ingest + fleet refine)
  through ``HostFleetBackend`` (numpy rings, full snapshot copied to the
  device every round) vs ``ShardedFleetBackend`` (device-resident rings
  over the ``sessions`` mesh, donated in-place ingest, shard_map refine).
  Reports per-shard refine throughput, mean/p50/p95 round latency
  (measured after an explicit warmup round so XLA compile never pollutes
  the numbers), and the measured host->device traffic: the sharded plane
  moves **zero** snapshot bytes per round;
- sessions/sec   — end-to-end admission → ingest → batched refine;
- requests/sec   — the batched two-sub-batch ``CascadeServer.handle``.

Prints the standard ``name,us_per_call,derived`` CSV rows plus one
``BENCH {...}`` JSON line for machine consumption.

    PYTHONPATH=src python -m benchmarks.fleet_serve [--quick] [--shards S]

``--shards S`` forces S host (CPU) devices (the env must not have
initialized jax yet — run as shown above) and shards the session axis
S ways.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import pcts, row

W, DIM, N_CLASSES = 100, 64, 10
SIZES = (1, 8, 32, 128)


def _head():
    def head_init(key):
        return {"w": 0.01 * jax.random.normal(key, (DIM, N_CLASSES))}

    def head_apply(p, z):
        return z @ p["w"]

    return head_init, head_apply


def _fill(insert, rng, *, drop=0.1):
    """Ingest W frames with ~10% network drops through `insert(t, z, label)`."""
    for t in range(W):
        if rng.random() < drop:
            continue
        insert(t, rng.normal(size=DIM).astype(np.float32), t % N_CLASSES)


def bench_refine(n, *, iters):
    """-> (sequential steps/s, fleet steps/s).  A "step" is one session's
    refinement; both paths share identical buffer contents."""
    from repro.core.fleet import FleetBuffer, FleetRefiner
    from repro.core.server import ServerRefiner, TemporalBuffer
    head_init, head_apply = _head()

    buffers = []
    fleet = FleetBuffer(capacity=n, window=W, dim=DIM)
    for i in range(n):
        rng = np.random.default_rng(i)
        buf = TemporalBuffer(window=W, dim=DIM)
        _fill(lambda t, z, l: buf.insert(t, z, label=l), rng)
        buffers.append(buf)
        sid = fleet.admit()
        rng = np.random.default_rng(i)
        _fill(lambda t, z, l: fleet.insert(sid, t, z, label=l), rng)

    srv = ServerRefiner(head_init, head_apply, lr=1e-2)
    flt = FleetRefiner(head_init, head_apply, lr=1e-2)

    def seq_round(i):
        for buf in buffers:
            srv.refine(jax.random.PRNGKey(i), buf)

    def fleet_round(i):
        flt.refine(jax.random.PRNGKey(i), fleet)

    out = []
    for fn in (seq_round, fleet_round):
        fn(0)                                   # warmup: compile
        t0 = time.perf_counter()
        for i in range(iters):
            fn(1 + i)
        dt = time.perf_counter() - t0
        out.append(n * iters / dt)
    return out


def bench_backends(n, *, iters, shards=1):
    """Host vs device-resident sharded data plane.

    One serving *round* = batched ingest of one frame per session +
    one fleet-wide refine.  The host path re-snapshots the whole
    ``(N, W, d)`` fleet to the device every round; the sharded path
    refines the rings where they already live (``snapshot_h2d == 0``) —
    the per-round traffic is measured off the backend counters, not
    assumed."""
    from repro.core.fleet import HostFleetBackend, ShardedFleetBackend
    from repro.launch.mesh import make_sessions_mesh
    head_init, head_apply = _head()
    out = {}
    for kind in ("host", "sharded"):
        if kind == "host":
            b = HostFleetBackend(capacity=n, window=W, dim=DIM,
                                 head_init=head_init, head_apply=head_apply,
                                 lr=1e-2)
        else:
            # pin the mesh to the requested shard count (NOT every
            # visible device: the env may force more than --shards)
            b = ShardedFleetBackend(capacity=n, window=W, dim=DIM,
                                    head_init=head_init,
                                    head_apply=head_apply, lr=1e-2,
                                    mesh=make_sessions_mesh(shards))
        rng = np.random.default_rng(0)
        sids = np.array([b.admit() for _ in range(n)])
        for t in range(W):                       # pre-fill, ~10% drops
            keep = rng.random(n) > 0.1
            if keep.any():
                m = int(keep.sum())
                b.insert_batch(sids[keep], np.full(m, t),
                               rng.normal(size=(m, DIM)).astype(np.float32),
                               np.full(m, t % N_CLASSES))

        def round_(i, t):
            b.insert_batch(sids, np.full(n, t),
                           rng.normal(size=(n, DIM)).astype(np.float32),
                           np.full(n, t % N_CLASSES))
            b.refine(jax.random.PRNGKey(i))

        # warmup: compile BOTH the full-batch ingest scatter and the
        # refine step before anything is timed
        round_(0, W)
        snap0, ing0 = b.snapshot_h2d_bytes, b.ingest_h2d_bytes
        round_ms = []
        t0 = time.perf_counter()
        for i in range(iters):
            t1 = time.perf_counter()
            round_(1 + i, W + 1 + i)
            round_ms.append((time.perf_counter() - t1) * 1e3)
        rounds_s = iters / (time.perf_counter() - t0)
        snap_rd = (b.snapshot_h2d_bytes - snap0) // iters
        ing_rd = (b.ingest_h2d_bytes - ing0) // iters
        round_pcts = pcts(round_ms)
        p50, p95 = round_pcts["p50"], round_pcts["p95"]
        out[kind] = {
            "shards": b.shards,
            "rounds_per_s": rounds_s,
            "session_steps_per_s": n * rounds_s,
            "per_shard_sessions": n // b.shards,
            "per_shard_steps_per_s": n // b.shards * rounds_s,
            "round_ms": round_pcts,
            "snapshot_h2d_bytes_per_round": snap_rd,
            "ingest_h2d_bytes_per_round": ing_rd,
        }
        tag = f"sharded{b.shards}" if kind == "sharded" else "host"
        row(f"fleet.backend.{tag}.N{n}", 1e6 / rounds_s,
            f"{n // b.shards * rounds_s:.1f} steps/s/shard, round p50 "
            f"{p50:.2f}ms p95 {p95:.2f}ms, snapshot h2d {snap_rd} B/round")
    assert out["sharded"]["snapshot_h2d_bytes_per_round"] == 0, \
        "device-resident refine must not copy the fleet snapshot"
    assert out["host"]["snapshot_h2d_bytes_per_round"] > 0
    return out


def bench_sessions(n, *, iters):
    """End-to-end fleet lifecycle: admit → ingest (batched) → refine →
    evict.  -> sessions/sec."""
    from repro.core.fleet import FleetBuffer, FleetRefiner
    head_init, head_apply = _head()
    fleet = FleetBuffer(capacity=n, window=W, dim=DIM)
    flt = FleetRefiner(head_init, head_apply, lr=1e-2)
    rng = np.random.default_rng(0)

    def lifecycle(i):
        sids = np.array([fleet.admit() for _ in range(n)])
        for t in range(W):
            keep = rng.random(n) > 0.1
            if keep.any():
                fleet.insert_batch(sids[keep], np.full(keep.sum(), t),
                                   rng.normal(size=(int(keep.sum()), DIM)),
                                   np.full(keep.sum(), t % N_CLASSES))
        flt.refine(jax.random.PRNGKey(i), fleet)
        for sid in sids:
            fleet.evict(sid)

    lifecycle(0)
    t0 = time.perf_counter()
    for i in range(iters):
        lifecycle(1 + i)
    return n * iters / (time.perf_counter() - t0)


def bench_cascade(batch, *, iters, seq=32):
    """Batched cascade serving -> requests/sec."""
    from dataclasses import replace
    from repro.configs.base import get_config, smoke_config
    from repro.launch.serve import CascadeServer
    from repro.models import lm
    small = smoke_config(get_config("qwen1.5-0.5b"))
    large = replace(smoke_config(get_config("qwen3-1.7b")),
                    vocab=small.vocab, d_model=small.d_model, n_layers=4)
    key = jax.random.PRNGKey(0)
    sp, _ = lm.init_lm(small, key)
    lp, _ = lm.init_lm(large, key)
    srv = CascadeServer(small, sp, large, lp, threshold="auto")
    toks = [jax.random.randint(jax.random.PRNGKey(i), (batch, seq), 0,
                               small.vocab) for i in range(iters + 1)]
    srv.handle(toks[0])
    t0 = time.perf_counter()
    for t in toks[1:]:
        srv.handle(t)
    return batch * iters / (time.perf_counter() - t0)


def run_all(*, quick=False, shards=1):
    sizes = [n for n in SIZES if not (quick and n > 32)]
    result = {"refine": {}, "sessions": {}, "cascade": {}, "backends": {},
              "shards": shards}
    for n in sizes:
        iters = max(3, 96 // n)
        seq_sps, fleet_sps = bench_refine(n, iters=iters)
        speedup = fleet_sps / seq_sps
        result["refine"][n] = {"sequential_steps_per_s": seq_sps,
                               "fleet_steps_per_s": fleet_sps,
                               "speedup": speedup}
        row(f"fleet.refine.seq.N{n}", 1e6 / seq_sps, "steps/s baseline")
        row(f"fleet.refine.batched.N{n}", 1e6 / fleet_sps,
            f"{speedup:.1f}x vs sequential")
    for n in sizes:
        if n % max(shards, 1):
            continue                     # capacity must divide the mesh
        result["backends"][n] = bench_backends(n, iters=max(3, 48 // n),
                                               shards=shards)
    for n in sizes:
        sps = bench_sessions(n, iters=max(2, 16 // n))
        result["sessions"][n] = {"sessions_per_s": sps}
        row(f"fleet.lifecycle.N{n}", 1e6 / sps, "admit+ingest+refine+evict")
    for b in sizes:
        rps = bench_cascade(b, iters=max(3, 48 // b))
        result["cascade"][b] = {"requests_per_s": rps}
        row(f"fleet.cascade.B{b}", 1e6 / rps, "two-tier batched handle")
    print("BENCH " + json.dumps({"bench": "fleet_serve", "window": W,
                                 "dim": DIM, **result}))
    return result


def force_host_devices(n):
    """Force ``n`` fake host devices for the ``sessions`` mesh.

    Must run before jax initializes its backend (importing jax is fine;
    querying devices is not) — both serving benchmarks call this from
    ``__main__`` before any device touch."""
    import os
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    if len(jax.devices()) < n:
        raise SystemExit(
            f"--shards {n} needs {n} devices but jax initialized with "
            f"{len(jax.devices())}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} in the "
            "environment instead")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the N=128 points")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the session axis over this many forced "
                         "host devices (ShardedFleetBackend)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    force_host_devices(args.shards)
    run_all(quick=args.quick, shards=args.shards)
