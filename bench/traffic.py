"""The one traffic generator: every mix is a JSON file of parameters
under ``bench/traffic/`` that this module reads.

A mix fixes the client population (sessions, the INTERACTIVE : STANDARD
: BULK split, the network profiles their links walk in), the split
policy's bandwidth tiers, and the loop:

- ``closed``: each streaming session keeps one frame outstanding and
  sends its next frame when the previous result arrives;
- ``open``: each session sends one 1-s window per second on average;
  arrivals over all sessions are a Poisson process of ``rate_per_s``.

Everything is drawn from the seed, and every seed gets the same sizes in
another order: the same number of sessions per class and per network
profile, the same multiset of inter-arrival gaps.
"""
from __future__ import annotations

import numpy as np

CLASSES = ("interactive", "standard", "bulk")

# Edge-client link profiles, copied from the program's control-plane
# environment (core/env.py, NET_PROFILES; paper section 5, six profiles
# over 4G/5G traces): the bandwidth band in Mbps and the random-walk
# step scale.
NET_PROFILES = {
    "stable": ((6.0, 10.0), 0.05),
    "wifi": ((30.0, 50.0), 0.05),
    "variable": ((3.0, 25.0), 0.25),
    "congested": ((1.0, 3.0), 0.15),
    "dropout": ((0.5, 20.0), 0.45),
    "5g": ((20.0, 50.0), 0.10),
}
BW_NORM = 50.0       # Mbps; the policy's bandwidth feature is bw / BW_NORM
TABLE = 256          # frames per session drawn ahead; later frames wrap
GAP_SEED = 20240601  # fixes the multiset of open-loop gaps for all seeds


def rng_for(seed, stream):
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


class BandwidthTierPolicy:
    """A batched split policy over the program's observation rows
    ``[u, cpu, bw / BW_NORM]``: the slower the link, the deeper the edge
    prefix.  ``ks`` lists the split points from the slowest tier to the
    fastest; the tiers split [0, 1] evenly.  Copied from the bring-up
    smoke run, where k = L below a third, L/2 in the middle, 0 above."""

    def __init__(self, L, ks):
        self.L = L
        self.ks = np.asarray(ks, np.int64)
        n = len(ks)
        self.edges = np.arange(1, n, dtype=np.float32) / n

    def decide(self, obs_batch):
        bw = np.asarray(obs_batch, np.float32)[:, 2]
        return self.ks[np.searchsorted(self.edges, bw, side="right")]

    def bandwidth_for(self, k):
        """A link speed in Mbps that this policy sends to split ``k``."""
        i = int(np.flatnonzero(self.ks == k)[0])
        return (i + 0.5) / len(self.ks) * BW_NORM


def split_counts(total, weights):
    """``total`` split in proportion to ``weights``, largest remainders
    first, so the counts sum to ``total``."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - out), kind="stable")[:total - out.sum()]:
        out[i] += 1
    return out


class Population:
    """The streaming sessions of a mix and what each sends.

    Per session: its QoS class, its network profile and, per frame index
    t (mod ``TABLE``), the link bandwidth of its walk, the uncertainty
    and the mel drawn from the pool."""

    def __init__(self, mix, n_sessions, seed, pool_size):
        rng = rng_for(seed, 1)
        counts = split_counts(n_sessions, mix["class_mix"])
        classes = np.repeat(np.arange(len(CLASSES)), counts)
        self.qos = rng.permutation(classes)
        names = sorted(NET_PROFILES)
        prof = np.arange(n_sessions) % len(names)
        self.profile = [names[i] for i in rng.permutation(prof)]
        lo = np.array([NET_PROFILES[p][0][0] for p in self.profile])
        hi = np.array([NET_PROFILES[p][0][1] for p in self.profile])
        vol = np.array([NET_PROFILES[p][1] for p in self.profile])
        # the environment's random walk (EdgeCloudEnv._bw_step): start
        # uniform in the band, step N(0, vol) x band width, clipped to
        # [lo / 2, 1.2 hi]
        bw = np.empty((n_sessions, TABLE), np.float64)
        bw[:, 0] = rng.uniform(lo, hi)
        steps = rng.normal(0.0, 1.0, (n_sessions, TABLE))
        for t in range(1, TABLE):
            bw[:, t] = np.clip(bw[:, t - 1] + steps[:, t] * vol * (hi - lo),
                               0.5 * lo, 1.2 * hi)
        self.bw = bw
        self.u = rng.uniform(0.0, 1.0, (n_sessions, TABLE))
        self.pool_idx = rng.integers(0, pool_size, (n_sessions, TABLE))
        self.n = n_sessions

    def sample(self, n, seed):
        """``n`` session indices drawn from the seed, as many from each
        network profile as the count allows, so that every split point
        the policy picks is among their frames."""
        rng = rng_for(seed, 5)
        names = sorted(set(self.profile))
        by = {p: rng.permutation([i for i in range(self.n)
                                  if self.profile[i] == p]).tolist()
              for p in names}
        out = []
        while len(out) < min(n, self.n):
            for p in names:
                if by[p] and len(out) < n:
                    out.append(by[p].pop())
        return out

    def frame(self, i, t, pool, labels, frame_cls):
        j = t % TABLE
        p = int(self.pool_idx[i, j])
        return frame_cls(t=t, mel=pool[p], label=int(labels[p]),
                         u=float(self.u[i, j]),
                         bandwidth_mbps=float(self.bw[i, j]))


def open_schedule(rate_per_s, duration_s, n_sessions, seed):
    """Arrival offsets (s, from the start) and the session of each
    arrival.  The gaps are one fixed multiset, exponential with mean
    1 / rate, put in a seeded order; sessions take arrivals in a seeded
    round-robin, so each sends one frame per n_sessions arrivals."""
    n = int(round(rate_per_s * duration_s))
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0 / rate_per_s, n)
    gaps = rng_for(seed, 2).permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    order = rng_for(seed, 3).permutation(n_sessions)
    sessions = order[np.arange(n) % n_sessions]
    t = np.arange(n) // n_sessions
    return due, sessions, t
