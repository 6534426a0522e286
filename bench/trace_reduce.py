"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time inside the measured window, device time per
compiled program, and the idle gaps attributed to what the host was
doing (the ``bench.*`` spans the harness writes with
``jax.profiler.TraceAnnotation``).

The window is the ``bench.window`` host span.  Busy time is the union
of the intervals in which a compiled program ran on a device, clipped
to the window and averaged over the devices that ran anything.

    python bench/trace_reduce.py <file.xplane.pb>
"""
from __future__ import annotations

import bisect
import json
import re
import sys

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "no_host_span"

# Programs whose trace name says nothing of what they are.  The split
# engine's edge and server stages are ``jax.jit(partial(...))`` and show
# as ``jit__unknown``; they are the only such programs on this path.
PROGRAM_NAMES = {
    "jit__unknown": "split_stage",
}

_DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_MODULE_LINES = ("XLA Modules",)


def program_name(raw):
    name = _ID_SUFFIX.sub("", raw).strip()
    return PROGRAM_NAMES.get(name, name)


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def device_programs(pd):
    """{device plane name: [(program, start_ns, end_ns)]} from each
    device's "XLA Modules" line."""
    out = {}
    for plane in pd.planes:
        if not _DEVICE_PLANE.fullmatch(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name in _MODULE_LINES:
                evs.extend((program_name(n), a, b) for n, a, b in
                           _events(line))
        out[plane.name] = sorted(evs, key=lambda e: e[1])
    return out


def host_spans(pd, prefix=SPAN_PREFIX):
    """[(name, start_ns, end_ns)] of the harness's own host spans."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(e for e in _events(line) if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def _union(intervals, lo, hi):
    """Merged [a, b) intervals clipped to [lo, hi)."""
    merged = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _gaps(busy, lo, hi):
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _attribute(gaps, spans):
    """Seconds of device idle time under each host span.  Where spans
    nest, the innermost (shortest) one that covers an instant gets it."""
    totals = {}
    starts = [s[1] for s in spans]
    longest = max((b - a for _, a, b in spans), default=0.0)
    for g0, g1 in gaps:
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_right(starts, g1)
        cover = [s for s in spans[lo:hi] if s[1] < g1 and s[2] > g0]
        cuts = sorted({g0, g1} | {x for _, a, b in cover for x in (a, b)
                                   if g0 < x < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [s for s in cover if s[1] <= mid < s[2]]
            name = (min(inner, key=lambda s: s[2] - s[1])[0] if inner
                    else NO_SPAN)
            totals[name] = totals.get(name, 0.0) + (b - a) * 1e-9
    return totals


def reduce(pd, *, top=10):
    """-> {"window_s", "busy_s", "devices", "programs": {name: s},
    "program_calls": {name: n}, "idle_by_span": {name: s},
    "breakdown": {"device_ops": [...], "idle_gaps": [...]}}.

    ``busy_s`` and every per-program time are averaged over the devices
    that ran a program in the window."""
    spans = host_spans(pd)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    progs = device_programs(pd)
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        evs = [e for v in progs.values() for e in v]
        if not evs:
            raise ValueError("the trace holds no device program and no "
                             f"{WINDOW_SPAN} span")
        lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    used = {d: evs for d, evs in progs.items()
            if any(b > lo and a < hi for _, a, b in evs)}
    n = max(len(used), 1)
    busy_s, programs, calls, idle = 0.0, {}, {}, {}
    for evs in used.values():
        busy = _union([(a, b) for _, a, b in evs], lo, hi)
        busy_s += sum(b - a for a, b in busy) * 1e-9 / n
        for name, a, b in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                programs[name] = programs.get(name, 0.0) + d * 1e-9 / n
                calls[name] = calls.get(name, 0) + 1
        for name, s in _attribute(_gaps(busy, lo, hi), inner).items():
            idle[name] = idle.get(name, 0.0) + s / n
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "devices": len(used),
        "programs": programs,
        "program_calls": {k: v / n for k, v in calls.items()},
        "idle_by_span": idle,
        "breakdown": {"device_ops": [list(kv) for kv in rank(programs)],
                      "idle_gaps": [list(kv) for kv in rank(idle)]},
    }


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit("usage: trace_reduce.py <file.xplane.pb>")
    print(json.dumps(reduce(load(argv[0])), indent=1))


if __name__ == "__main__":
    main()
