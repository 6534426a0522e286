"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (value semantics per row name:
KB, ms, mJ, %, correlation r, ... — the derived column carries the paper's
number for side-by-side comparison), and writes the machine-readable
serving-perf trajectories CI uploads as artifacts so performance is
tracked across PRs: ``BENCH_gateway.json`` (frames/s, syncs/tick, staged
H2D bytes, p50/p95 tick latency at N ∈ {32, 64}; docs/PERF.md),
``BENCH_stream.json`` (sustained streaming frames/s, per-class p95 queue
waits, deadline-miss rates, preemption counts, syncs/tick;
docs/STREAMING.md), ``BENCH_cluster.json`` (federation drain lane:
migration pause p50/p95 ms, frames/s before/during/after a live drain,
migrated volume; docs/FEDERATION.md), and ``BENCH_obs.json`` (telemetry
plane: asserted <2% tracing-off overhead, schema-validated Prometheus
export, flight-recorder exactness; docs/OBSERVABILITY.md).

    PYTHONPATH=src python -m benchmarks.run [--quick|--smoke] [--only PREFIX]

``--smoke`` is the CI configuration: the fewest iterations that still
exercise every bit-parity assert (a benchmark whose parity assert trips
fails the process loudly — that is the point of running it in CI).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only benches whose module matches")
    ap.add_argument("--quick", action="store_true",
                    help="skip the slowest (training-based) benches")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config (implies --quick)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    quick = args.quick or args.smoke

    from benchmarks import (cluster_serve, fleet_serve, gateway_serve,
                            kernels_bench, obs_bench, quality_tables,
                            stream_serve, system_tables)
    print("name,us_per_call,derived")
    t0 = time.time()

    def gateway():
        out = gateway_serve.run_all(quick=quick, smoke=args.smoke)
        path = gateway_serve.write_bench_json(out)
        print(f"# wrote {path}", file=sys.stderr)

    def stream():
        out = stream_serve.run_all(quick=quick, smoke=args.smoke)
        path = stream_serve.write_bench_json(out)
        print(f"# wrote {path}", file=sys.stderr)

    def cluster():
        out = cluster_serve.run_all(quick=quick, smoke=args.smoke)
        path = cluster_serve.write_bench_json(out)
        print(f"# wrote {path}", file=sys.stderr)

    def obs():
        out = obs_bench.run_all(quick=quick, smoke=args.smoke)
        path = obs_bench.write_bench_json(out)
        print(f"# wrote {path}", file=sys.stderr)

    suites = [("system", system_tables.run_all),
              ("kernels", kernels_bench.run_all),
              ("fleet", lambda: fleet_serve.run_all(quick=quick)),
              ("gateway", gateway),
              ("stream", stream),
              ("cluster", cluster),
              ("obs", obs)]
    if not quick:
        suites.insert(1, ("quality", quality_tables.run_all))
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        fn()
    print(f"# total {time.time() - t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
