#!/usr/bin/env python3
"""Run one cell of the StreamSplit chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, their configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is the result: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device`` and, last,
``checks`` (each number compared, with its limit; the same lines end
standard error).  Without a TPU, or with fewer chips than the cell
needs, it exits non-zero and prints no result.

``--control 1`` puts the reference's control (the configuration computed
in bfloat16) in the program's place: its embeddings are judged instead
of the served ones, so ``correct`` comes out false.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    out = harness.run(spec, args.workload, args.seed, args.seconds,
                      args.trace, t_start=T_START,
                      control=bool(args.control), keep_trace=args.keep_trace)
    sys.stdout.flush()
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
