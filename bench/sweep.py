#!/usr/bin/env python3
"""Find the knee of the open-loop serving cell on the chip, in one
process.

    python3 bench/sweep.py --rates 3000,3500,4000 --repeats 3 \
        --seconds 5 --seed 1

Builds ``audio.serve.steady``'s configuration once (weights, fleet,
every tick composition warmed), then offers each rate in turn, with as
many streaming sessions as the rate at one 1-s window per second each,
``--repeats`` times, each with the arrivals of another seed, and prints
one row per window: offered and served frames/s, the p95 from due time
to result of INTERACTIVE and of all frames, how late the generator ran,
the backlog when the window closed, refusals, and the programs built in
the window.

A window is sustained when the p95 of all frames stays within the
STANDARD class's 250 ms deadline, nothing is refused, the backlog at the
close is at most two ticks and at least 97% of the offered rate is
served.  A rate holds only when most of its windows are sustained, so
that one host stall does not decide it.  The knee is the highest rate
below the first that fails; the cell runs at 4/5 of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "metrics"))

import _latency  # noqa: E402
import harness  # noqa: E402
import traffic as tr  # noqa: E402

WORKLOAD = "audio.serve.steady"
DEADLINE_MS = 250.0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered frames/s, ascending")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    rates = [int(x) for x in args.rates.split(",")]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.Cell(json.load(f), WORKLOAD)
    jax = harness.configure_jax()
    devices = harness.chip_devices(cell.chips)
    compiles = harness.CompileLog()
    cfg, mix = cell.cfg, dict(cell.mix)
    enc = cfg["encoder"]
    params = jax.jit(lambda key: cell.reference.init_params(enc, key))(
        harness.seed_key(args.seed))
    pool, labels = harness.pool_of(cfg, mix, args.seed)
    # one 1-s window per second per session, up to 70% of the rings;
    # past that the sessions send faster (BULK admission keeps a quarter
    # of the rings free)
    cap = cfg["fleet"]["capacity"]
    most = int(0.7 * cap)
    n_max = min(int(max(rates) * mix["frame_period_s"]), most)
    pop = tr.Population(mix, n_max, args.seed, len(pool))
    holder = {}
    system = harness.System(cfg, mix, params,
                            lambda r: holder["t"].on_result(r), devices)
    n_quiet = max(int(cap * cfg["fleet"]["open_fraction"]) - n_max,
                  system.max_batch)
    sids, quiet = system.open_sessions([tr.CLASSES[c] for c in pop.qos],
                                       n_quiet)
    mb = system.max_batch
    system.warm(harness.warm_plan(mix["k_tiers"], range(1, mb + 1), mb),
                quiet, pool, labels)
    spans = harness.Spans(annotate=False)
    spans.wrap(system.gw, "tick_launch", "bench.tick_launch")
    pauses = harness.Pauses()
    rows = []
    print("| offered/s | window | served/s | p95 interactive ms | p95 all ms | "
          "gen late p95 ms | backlog at close | refused | compiles | "
          "sustained |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for rate in rates:
        mix["rate_per_s"] = rate
        n = min(int(rate * mix["frame_period_s"]), most)
        for rep in range(args.repeats):
            tfc = harness.Traffic(mix, pool, labels, pop, sids[:n])
            holder["t"] = tfc
            run = harness.Run(cell)
            run.window, run.stats, _, run.arrivals = harness.run_open(
                system, tfc, args.seconds, args.seed + 1 + rep)
            w0, w1 = run.window
            t = np.asarray(tfc.times)
            served = float(np.count_nonzero((t >= w0) & (t < w1))
                           / (w1 - w0))
            sel = _latency.due_in_window(run)
            st = run.stats["w1"]
            row = {
                "offered": rate, "window": rep, "served": served,
                "p95_interactive_ms": _latency.p95(
                    _latency.latency_ms(run, qos=0)),
                "p95_all_ms": _latency.p95(_latency.latency_ms(run)),
                "gen_late_p95_ms": float(np.percentile(
                    run.arrivals["late"][sel], 95) * 1e3),
                "backlog": int(sum(st.queue_depth.values())
                               + sum(st.in_flight.values())),
                "refused": int(run.arrivals["refused"].sum()),
                "compiles": compiles.count(w0, w1),
            }
            row["sustained"] = bool(
                row["p95_all_ms"] <= DEADLINE_MS and not row["refused"]
                and row["backlog"] <= 2 * mb
                and served >= 0.97 * rate)
            run.spans, run.compiles = spans, compiles
            harness.diagnose(run, [("start", w0)], pauses)
            rows.append(row)
            print(f"| {rate} | {rep} | {served:.1f} | "
                  f"{row['p95_interactive_ms']:.2f} | "
                  f"{row['p95_all_ms']:.2f} | {row['gen_late_p95_ms']:.2f} | "
                  f"{row['backlog']} | {row['refused']} | "
                  f"{row['compiles']} | {row['sustained']} |", flush=True)
    knee = None
    for rate in rates:
        ok = [r["sustained"] for r in rows if r["offered"] == rate]
        if 2 * sum(ok) <= len(ok):
            break
        knee = rate
    print(json.dumps({"rows": rows, "knee": knee,
                      "rate_at_four_fifths": knee and 0.8 * knee}))


if __name__ == "__main__":
    main()
