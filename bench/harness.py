"""One run of one benchmark cell: set-up, the measured window, the check.

``run(spec, workload, seed, seconds, trace)`` builds the cell's
configuration behind the program's serving path (``StreamServer`` ->
``StreamSplitGateway`` -> ``ShardedFleetBackend`` on a one-device
sessions mesh), warms every program the cell's traffic can launch,
drives the traffic mix for ``seconds``, drains, compares what was served
against the configuration's plain reference, and returns the result
line.  Which metrics it reports, and how each is read, comes from
``BENCHMARK.json`` and the files under ``bench/``:

- ``bench/configs/<file>.json`` the sizes, ``<file>.reference.py``
  beside it the reference and the weights;
- ``bench/traffic/<traffic>.json`` the mix, read by ``traffic.py``;
- ``bench/metrics/<metric>.py`` one reader per metric, ``read(run)``
  returning the number or ``None`` where there is nothing to read.
"""
from __future__ import annotations

import gc
import importlib.util
import itertools
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

import traffic as tr

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
# A --trace 1 run profiles the last TRACE_S seconds of its window: the
# profiler's cost of collecting and reading a trace grows with its length,
# and on one TPU v5e a traced 51-s window took a run to 315 s of its 360.
TRACE_S = 10.0


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    name = "bench_" + os.path.basename(path).replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, mix and
    the metrics it reports."""

    def __init__(self, spec, workload, traffic_dir=None):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"bench: no workload {workload!r} in "
                             f"BENCHMARK.json ({sorted(cells)})")
        self.spec = spec
        self.w = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_path = os.path.join(ROOT,
                                        configs[self.w["config"]]["file"])
        self.cfg = load_json(self.config_path)
        self.mix = load_json(os.path.join(
            traffic_dir or os.path.join(BENCH, "traffic"),
            self.w["traffic"] + ".json"))
        self.reference = load_module(os.path.join(
            os.path.dirname(self.config_path), self.cfg["reference"]))
        self.chips = int(self.w["chips"])

    def _mine(self, m):
        return "workloads" not in m or self.name in m["workloads"]

    def end_to_end(self):
        return [m for m in self.spec["end_to_end"] if self._mine(m)]

    def per_layer(self):
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def configure_jax():
    """The persistent compile cache at a fixed path in the checkout, for
    every program however fast it compiles; libtpu's logs off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return jax


def chip_devices(chips, require_chip=True):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{devs[0].platform!r}); nothing was measured")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, "
                         f"{len(devs)} found")
    return devs[:chips]


def seed_key(seed):
    """A PRNG key from any whole-number seed (all 64 bits count)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


class CompileLog:
    """Times at which this process built an XLA program: compiled it, or
    loaded it from the persistent cache."""

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.times.append(time.perf_counter())

    def count(self, t0, t1):
        return sum(t0 <= t < t1 for t in self.times)


class Pauses:
    """Garbage-collector pauses of this process: (start, seconds,
    generation), for the run's diagnostics."""

    def __init__(self):
        self.events, self._t0 = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        elif self._t0 is not None:
            self.events.append((self._t0, now - self._t0, info["generation"]))

    def close(self):
        gc.callbacks.remove(self._on)


def diagnose(r, marks, pauses):
    """One stderr line of what the window saw besides the metrics: the
    set-up phases, the longest stall between tick launches, collector
    pauses, programs built in the window and the ticks launched in each
    5 s of it."""
    w0, w1 = r.window
    launch = [s for s in r.spans.spans.get("bench.tick_launch", ())
              if w0 <= s[0] < w1]
    collect = r.spans.spans.get("bench.tick_collect", ())
    starts = [a for a, _ in launch]
    gaps = sorted(((b - a, a - w0) for a, b in zip(starts, starts[1:])),
                  reverse=True)[:3]
    longest = lambda spans: max((b - a for a, b in spans
                                 if w0 <= a < w1), default=0.0) * 1e3
    gcs = [(d, g, t - w0) for t, d, g in pauses.events if w0 <= t < w1]
    worst = max(gcs, default=(0.0, -1, 0.0))
    phases = " ".join(f"{name} {t - prev:.2f}s" for (name, t), (_, prev)
                      in zip(marks[1:], marks))
    edges = np.arange(w0, w1 + 1e-6, 5.0)
    per5 = np.histogram(starts, bins=edges)[0] if len(edges) > 1 else []
    print(f"bench: set-up {phases}; window {w1 - w0:.3f}s, "
          f"{len(starts)} ticks, longest launch-to-launch gaps (ms at s): "
          + ", ".join(f"{g * 1e3:.1f}@{at:.2f}" for g, at in gaps)
          + f"; longest launch {longest(launch):.1f} ms, collect "
          f"{longest(collect):.1f} ms; gc {len(gcs)} pauses, {sum(d for d, _, _ in gcs) * 1e3:.1f}"
          f" ms in all, longest {worst[0] * 1e3:.1f} ms (gen {worst[1]}) at "
          f"{worst[2]:.2f}s; programs built in the window "
          f"{r.compiles.count(w0, w1)}; ticks a 5 s: "
          f"{' '.join(map(str, per5))}", file=sys.stderr)


class Spans:
    """The harness's own spans around calls into the program's layers,
    kept in memory; with ``annotate`` also written into the profiler's
    trace as ``jax.profiler.TraceAnnotation``s."""

    def __init__(self, annotate):
        self.annotate = annotate
        self.spans = {}
        self.launches = []      # (t0, frames, [(k, padded rows)])

    def wrap(self, obj, attr, name, after=None):
        import jax
        fn = getattr(obj, attr)
        rec = self.spans.setdefault(name, [])
        annotate = self.annotate

        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            if annotate:
                with jax.profiler.TraceAnnotation(name):
                    out = fn(*a, **kw)
            else:
                out = fn(*a, **kw)
            rec.append((t0, time.perf_counter()))
            if after is not None:
                after(t0, out)
            return out

        setattr(obj, attr, wrapped)

    def in_window(self, name, w0, w1):
        return [(a, b) for a, b in self.spans.get(name, ()) if w0 <= a < w1]


def hold(end, trace_dir=None):
    """Sleep until ``end`` on the host clock: the measured window.  With
    ``trace_dir``, profile its last ``TRACE_S`` seconds into that
    directory, inside a ``bench.window`` annotation, and return the
    traced (t0, t1); the caller stops the profiler."""
    if trace_dir is None:
        time.sleep(max(0.0, end - time.perf_counter()))
        return None
    import jax
    from jax.profiler import ProfileOptions
    time.sleep(max(0.0, end - TRACE_S - time.perf_counter()))
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(max(0.0, end - time.perf_counter()))
    return t0, time.perf_counter()


def pad_pow2(n):
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _min_rows(p):
    return 1 if p == 1 else p // 2 + 1


def warm_plan(ks, sizes, max_batch):
    """Tick compositions ({k: frames}) that between them launch every
    program the gateway's tick can build for frames at split points
    ``ks`` and tick sizes ``sizes``.

    A tick of B frames with n_k frames at each k builds programs keyed
    by: the padded tick pad(B) and each padded bucket pad(n_k) (the
    per-bucket gathers), (k, pad(n_k)) (the edge, wire and server
    stages), the tuple of padded buckets (their concatenation), the sum
    of the padded buckets with pad(B) (the reassembly gather), and B
    itself (the ring-ingest slice and pad)."""
    ks = sorted(ks)
    pows = [1 << i for i in range(pad_pow2(max_batch).bit_length())]
    sizes = sorted(set(sizes))

    def keys(counts):
        b = sum(counts.values())
        pb = pad_pow2(b)
        pads = tuple(pad_pow2(counts[k]) if counts.get(k) else 0
                     for k in ks)
        out = {("B", b), ("concat", pads), ("final", sum(pads), pb)}
        for k, p in zip(ks, pads):
            if p:
                out |= {("gather", pb, p), ("chain", k, p)}
        return out

    combos = []
    for pads in itertools.product([0] + pows, repeat=len(ks)):
        if not any(pads):
            continue
        lo = sum(_min_rows(p) for p in pads if p)
        hi = min(sum(pads), max_batch)
        for b in sizes:
            if lo <= b <= hi:
                counts, extra = {}, b - lo
                for k, p in zip(ks, pads):
                    if p:
                        add = min(extra, p - _min_rows(p))
                        counts[k] = _min_rows(p) + add
                        extra -= add
                combos.append(counts)
    covered, plan = set(), []
    for counts in combos:
        new = keys(counts) - covered
        if new:
            plan.append(counts)
            covered |= new
    return plan


def pool_of(cfg, mix, seed):
    """Seeded mel pool and its class labels, made on the device in one
    program: class templates plus noise, one 1-s window each."""
    import jax
    import jax.numpy as jnp
    enc = cfg["encoder"]
    n, classes = mix["pool_size"], mix["label_classes"]
    shape = (enc["frames"], enc["n_mels"])

    @jax.jit
    def make(key):
        kt, kn = jax.random.split(key)
        templates = jax.random.normal(kt, (classes,) + shape)
        labels = jnp.arange(n) % classes
        noise = jax.random.normal(kn, (n,) + shape)
        return templates[labels] + 0.5 * noise, labels

    mels, labels = make(jax.random.fold_in(seed_key(seed), 1))
    return np.asarray(mels), np.asarray(labels)


class System:
    """The program under test, built as the configuration states."""

    def __init__(self, cfg, mix, params, on_result, devices):
        from repro.api import StreamSplitGateway
        from repro.core.fleet_backend import ShardedFleetBackend
        from repro.launch.mesh import make_sessions_mesh
        from repro.models.audio_encoder import AudioEncCfg
        from repro.serving import SchedulerCfg, StreamServer
        enc = {k: tuple(v) if isinstance(v, list) else v
               for k, v in cfg["encoder"].items()}
        self.enc_cfg = AudioEncCfg(**enc)
        fleet = cfg["fleet"]
        self.backend = ShardedFleetBackend(
            capacity=fleet["capacity"], window=fleet["window"],
            dim=self.enc_cfg.d_embed, mesh=make_sessions_mesh(len(devices)))
        self.policy = tr.BandwidthTierPolicy(self.enc_cfg.n_blocks,
                                             mix["k_tiers"])
        self.gw = StreamSplitGateway(self.enc_cfg, params,
                                     policy=self.policy,
                                     backend=self.backend,
                                     refine_every=0)
        self.max_batch = cfg["scheduler"]["max_batch"]
        self.server = StreamServer(
            self.gw, cfg=SchedulerCfg(max_batch=self.max_batch),
            queue_maxlen=cfg["scheduler"]["queue_maxlen"],
            on_result=on_result)

    def open_sessions(self, qos_names, n_quiet):
        """Streaming sessions in the given classes, then quiet STANDARD
        ones (open, sending nothing during the run)."""
        from repro.api import QoSClass
        sids = [self.server.open_session(qos=QoSClass(q)).sid
                for q in qos_names]
        quiet = [self.server.open_session(qos=QoSClass.STANDARD).sid
                 for _ in range(n_quiet)]
        return sids, quiet

    def warm(self, plan, sids, pool, labels):
        """Run every planned tick composition through the gateway's own
        ``submit``/``tick``, on quiet sessions."""
        from repro.api import FrameRequest
        for t, counts in enumerate(plan):
            j = 0
            for k, n in counts.items():
                bw = self.policy.bandwidth_for(k)
                for _ in range(n):
                    p = (t * self.max_batch + j) % len(pool)
                    self.gw.submit(sids[j], FrameRequest(
                        t=t, mel=pool[p], label=int(labels[p]),
                        bandwidth_mbps=bw))
                    j += 1
            self.gw.tick()

    def rings(self, sids):
        b = self.backend
        idx = np.asarray(sids)
        return (np.asarray(b.z[idx]), np.asarray(b.t[idx]),
                np.asarray(b.label[idx]), np.asarray(b.newest[idx]))

    def free(self):
        b = self.backend
        for a in (b.z, b.t, b.label, b.newest, b.active_dev):
            a.delete()
        self.server = self.gw = self.backend = None


class Run:
    """What one run measured; the metric readers read it."""

    def __init__(self, cell):
        self.enc = cell.cfg["encoder"]
        self.window = None          # (w0, w1) on the host clock
        self.traced = None          # the profiled tail of the window
        self.setup_s = None
        self.result_times = None    # delivery time of every result
        self.arrivals = None        # open loop: per-arrival records
        self.stats = {}             # StreamStats at "w0", "w1", "end"
        self.spans = None
        self.compiles = None
        self.trace = None           # trace_reduce.reduce(...) or None
        self.device_kind = None
        self.max_batch = cell.cfg["scheduler"]["max_batch"]

    def launches_traced(self):
        """[(t0, frames, [(k, padded rows)])] of the ticks launched in
        the traced part of the window."""
        t0, t1 = self.traced
        return [x for x in self.spans.launches if t0 <= x[0] < t1]

    def frames_launched(self):
        return sum(n for _, n, _ in self.launches_traced())


class Traffic:
    """Drives a mix against a ``System`` and records what came back."""

    def __init__(self, mix, pool, labels, pop, sids):
        from repro.api import FrameRequest
        self.mix, self.pool, self.labels = mix, pool, labels
        self.pop, self.sids = pop, sids
        self.index = {sid: i for i, sid in enumerate(sids)}
        self.FrameRequest = FrameRequest
        self.times = []           # result delivery times
        self.kept = {}            # sid -> [(t, k, z)] for checked sessions
        self.keep = set()
        self.stop = False
        self.sent = 0
        self.refused = 0
        self.server = None
        self.next_t = np.zeros(len(sids), np.int64)
        self.arrival_done = None

    def frame(self, i, t):
        return self.pop.frame(i, t, self.pool, self.labels,
                              self.FrameRequest)

    def on_result(self, r):
        now = time.perf_counter()
        self.times.append(now)
        if r.sid in self.keep:
            self.kept.setdefault(r.sid, []).append((r.t, r.k, r.z))
        if self.arrival_done is not None:
            i = self.index[r.sid]
            self.arrival_done[r.t * len(self.sids) + self.inv[i]] = now
        elif not self.stop:
            i = self.index[r.sid]
            t = self.next_t[i]
            self.next_t[i] = t + 1
            self.sent += 1
            self.server.submit(r.sid, self.frame(i, int(t)))


def run_closed(system, tfc, seconds, trace_dir=None):
    """Each streaming session keeps one frame outstanding; the window
    opens once ``ramp_frames`` results have come back."""
    server = system.server
    tfc.server = server
    for i, sid in enumerate(tfc.sids):
        server.submit(sid, tfc.frame(i, 0))
    tfc.next_t[:] = 1
    tfc.sent = len(tfc.sids)
    server.start()
    while len(tfc.times) < tfc.mix["ramp_frames"]:
        server.served_total            # raises if the serving loop died
        time.sleep(0.002)
    stats = {"w0": server.stats()}
    w0 = time.perf_counter()
    traced = hold(w0 + seconds, trace_dir)
    w1 = time.perf_counter()
    stats["w1"] = server.stats()
    tfc.stop = True
    server.stop(drain=True)
    stats["end"] = server.stats()
    return (w0, w1), stats, traced


def run_open(system, tfc, seconds, seed, trace_dir=None):
    """Poisson arrivals at the mix's rate, ``ramp_s`` before the window
    and ``seconds`` in it, each timed from when it was due."""
    from repro.serving import QueueFullError
    mix = tfc.mix
    server = system.server
    tfc.server = server
    n_s = len(tfc.sids)
    ramp = mix["ramp_s"]
    due, sess, tt = tr.open_schedule(mix["rate_per_s"], ramp + seconds, n_s,
                                     seed)
    n = len(due)
    tfc.inv = np.empty(n_s, np.int64)
    tfc.inv[sess[:n_s]] = np.arange(n_s)
    tfc.arrival_done = np.full(n, np.nan)
    late = np.full(n, np.nan)
    refused = np.zeros(n, bool)
    server.start()
    base = time.perf_counter() + 0.2
    sids, frame = tfc.sids, tfc.frame

    errors = []

    def generate():
        try:
            send()
        except BaseException as e:      # re-raised once joined
            errors.append(e)

    def send():
        for j in range(n):
            target = base + due[j]
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
                now = time.perf_counter()
            late[j] = now - target
            i = int(sess[j])
            try:
                server.submit(sids[i], frame(i, int(tt[j])))
            except QueueFullError:
                refused[j] = True

    gen = threading.Thread(target=generate, name="bench-generator")
    gen.start()
    w0 = base + ramp
    time.sleep(max(0.0, w0 - time.perf_counter()))
    stats = {"w0": server.stats()}
    w0 = time.perf_counter()
    traced = hold(base + ramp + seconds, trace_dir)
    w1 = time.perf_counter()
    stats["w1"] = server.stats()
    gen.join()
    if errors:
        raise errors[0]
    server.stop(drain=True)
    stats["end"] = server.stats()
    tfc.sent = n
    tfc.refused = int(refused.sum())
    qos = tfc.pop.qos[sess]
    return (w0, w1), stats, traced, {
        "due": base + due, "done": tfc.arrival_done, "late": late,
        "refused": refused, "qos": qos}


def served_frames(cell, system, tfc):
    """What the timed path produced for the checked sessions, read back
    after the drain: [(pool index, k, served z)], and the number of ring
    slots (embedding, frame index, label, ``newest``) that do not hold
    exactly the newest served frame."""
    enc = cell.cfg["encoder"]
    window = cell.cfg["fleet"]["window"]
    sids = sorted(tfc.keep)
    ring_z, ring_t, ring_l, ring_new = system.rings(sids)
    frames, ring_bad = [], 0
    for r, sid in enumerate(sids):
        got = sorted(tfc.kept.get(sid, []), key=lambda e: e[0])
        i = tfc.index[sid]
        want_t = np.full(window, np.iinfo(np.int32).min, np.int64)
        want_z = np.zeros((window, enc["d_embed"]), np.float32)
        want_l = np.full(window, -1, np.int64)
        for t, k, z in got:
            p = int(tfc.pop.pool_idx[i, t % tr.TABLE])
            want_t[t % window], want_z[t % window] = t, z
            want_l[t % window] = int(tfc.labels[p])
            frames.append((p, int(k), z))
        newest = got[-1][0] if got else -1
        ring_bad += int((ring_t[r] != want_t).sum()
                        + (ring_l[r] != want_l).sum()
                        + (ring_z[r] != want_z).any(axis=1).sum()
                        + (ring_new[r] != newest))
    return frames, ring_bad


def embedder(cell, pool, frames, seed):
    """``embed(fn)``: the embeddings that ``fn(enc, params, mel, k)`` of
    the reference module gives the checked frames, run per k in blocks
    of the configuration's ``block`` rows, with weights made anew from
    the seed."""
    import jax
    import jax.numpy as jnp
    enc = cell.cfg["encoder"]
    block = cell.cfg["check"]["block"]
    params = jax.jit(lambda key: cell.reference.init_params(enc, key))(
        seed_key(seed))

    def embed(fn):
        out = {}
        for k in sorted({f[1] for f in frames}):
            sel = [j for j, f in enumerate(frames) if f[1] == k]
            run = jax.jit(lambda p, m, k=k: fn(enc, p, m, k))
            for lo in range(0, len(sel), block):
                part = sel[lo:lo + block]
                mel = np.stack([pool[frames[j][0]] for j in part])
                if len(part) < block:
                    mel = np.concatenate([mel, np.repeat(
                        mel[:1], block - len(part), axis=0)])
                out.update(zip(part, np.asarray(run(params, jnp.asarray(mel)))))
        return np.stack([out[j] for j in range(len(frames))])

    return embed


def check(run, cell, system, tfc, seed, control):
    """Compare what the timed path produced with the plain reference.
    Returns {name: (value, limit)}; every value must be <= its limit.

    With ``control`` the reference's control (the configuration computed
    one precision below the one it states) is put in the program's
    place: its embeddings are judged instead of the served ones."""
    ck = cell.cfg["check"]
    frames, ring_bad = served_frames(cell, system, tfc)
    unserved = tfc.sent - tfc.refused - len(tfc.times)
    system.free()
    gc.collect()
    pick = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7]).permutation(
        len(frames))[:ck["frames"]]
    frames = [frames[j] for j in sorted(pick)]
    embed = embedder(cell, tfc.pool, frames, seed)
    ref = cell.reference
    want = embed(ref.reference)
    served = np.stack([z for _, _, z in frames])
    got = embed(ref.control) if control else served
    gap = np.linalg.norm(got - want, axis=1)
    ks = sorted({k for _, k, _ in frames})
    by_k = {k: sum(f[1] == k for f in frames) for k in ks}
    print(f"bench: checked {len(frames)} {'control' if control else 'served'}"
          f" frames by k {by_k} against the reference (widest gap "
          f"{float(gap.max())!r}); {len(tfc.keep)} sessions' rings",
          file=sys.stderr)
    if control:
        print("bench: in this control run the program's own embed_gap_mean "
              f"reads {float(np.linalg.norm(served - want, axis=1).mean())!r}",
              file=sys.stderr)
    return {"embed_gap_mean": (float(gap.mean()),
                               ck["embed_gap_mean_limit"]),
            "ring_mismatch": (ring_bad, 0),
            "unserved": (unserved, 0),
            "k_unchecked": (len(set(cell.mix["k_tiers"]) - set(ks)), 0)}


def _metrics(run, entries):
    out = {}
    for m in entries:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        v = mod.read(run)
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            raise RuntimeError(f"metric {m['name']} read {v}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(spec, workload, seed, seconds, trace, *, t_start=None,
        require_chip=True, control=False, keep_trace=None,
        traffic_dir=None, inject=None):
    """One run of ``workload``; -> the result line (a dict).

    ``inject``, for the benchmark's own tests, is called with the built
    ``System`` before the traffic starts, to break the timed path."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(spec, workload, traffic_dir=traffic_dir)
    marks = [("start", t_start)]
    jax = configure_jax()
    devices = chip_devices(cell.chips, require_chip)
    marks.append(("jax", time.perf_counter()))
    compiles = CompileLog()
    pauses = Pauses()
    spans = Spans(annotate=bool(trace))
    cfg, mix = cell.cfg, cell.mix
    enc = cfg["encoder"]
    params = jax.jit(lambda key: cell.reference.init_params(enc, key))(
        seed_key(seed))
    pool, labels = pool_of(cfg, mix, seed)
    marks.append(("weights+pool", time.perf_counter()))
    n_stream = mix["sessions"] if mix["loop"] == "closed" else int(
        round(mix["rate_per_s"] * mix["frame_period_s"]))
    pop = tr.Population(mix, n_stream, seed, len(pool))
    capacity = cfg["fleet"]["capacity"]
    n_quiet = max(int(capacity * cfg["fleet"]["open_fraction"]) - n_stream,
                  cfg["scheduler"]["max_batch"])
    holder = {}
    system = System(cfg, mix, params, lambda r: holder["t"].on_result(r),
                    devices)
    sids, quiet = system.open_sessions(
        [tr.CLASSES[c] for c in pop.qos], n_quiet)
    tfc = Traffic(mix, pool, labels, pop, sids)
    holder["t"] = tfc
    tfc.keep = {sids[i] for i in pop.sample(mix["check_sessions"], seed)}
    marks.append(("fleet+sessions", time.perf_counter()))
    max_batch = system.max_batch
    sizes = ([max_batch] if mix["tick_sizes"] == "full"
             else range(1, max_batch + 1))
    plan = warm_plan(mix["k_tiers"], sizes, max_batch)
    system.warm(plan, quiet, pool, labels)
    marks.append((f"warm({len(plan)} ticks)", time.perf_counter()))
    spans.wrap(system.gw, "tick_launch", "bench.tick_launch",
               after=lambda t0, plan: spans.launches.append(
                   (t0, len(plan), [(k, pad_pow2(len(idx)))
                                    for k, idx, *_ in plan.launched])))
    spans.wrap(system.gw, "tick_collect", "bench.tick_collect")
    if inject is not None:
        inject(system)
    r = Run(cell)
    r.device_kind = devices[0].device_kind
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if mix["loop"] == "closed":
        r.window, r.stats, r.traced = run_closed(system, tfc, seconds,
                                                 trace_dir)
    else:
        r.window, r.stats, r.traced, r.arrivals = run_open(
            system, tfc, seconds, seed, trace_dir)
    r.setup_s = r.window[0] - t_start
    marks.append(("ramp", r.window[0]))
    pauses.close()
    if trace:
        jax.profiler.stop_trace()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    r.result_times = np.asarray(tfc.times)
    r.spans, r.compiles = spans, compiles
    diagnose(r, marks, pauses)
    if trace:
        import glob
        import trace_reduce
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        r.trace = trace_reduce.reduce(trace_reduce.load(files[0]))
        if keep_trace:
            shutil.copy(files[0], keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    checks = check(r, cell, system, tfc, seed, control)
    correct = all(v <= lim for v, lim in checks.values())
    entries = cell.per_layer() if trace else cell.end_to_end()
    metrics = _metrics(r, entries)
    attempted = int(tfc.sent)
    failed = int(tfc.refused + max(0, checks["unserved"][0]))
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = r.trace["busy_s"]
        device["window_s"] = r.trace["window_s"]
        out["breakdown"] = r.trace["breakdown"]
    out["checks"] = {name: {"value": float(v), "limit": float(lim)}
                     for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        print(f"check {name}: {float(v)!r} (limit {float(lim)!r})",
              file=sys.stderr)
    return out
