"""Wall-clock traffic generators, driven by the parameters of a traffic file.

Every arrival has a due time in seconds from the start of the measured
window; a driver hands an item to the system once its due time has passed
and times it from that due time, so a stalled tick adds to the latency of
everything that fell due during the stall (open loop).

The shape of the traffic (when each client's bursts come, how many
requests arrive, where each client walks, when each mapper sends a
keyframe) is drawn from the traffic file's fixed ``shape_seed``.  The run's
``--seed`` only permutes which client plays which timeline and draws the
content (embeddings, objects touched).  Every seed therefore offers the
same amount of work in the same rhythm, in another order.

The query process is the Markov-modulated Poisson process of
``repro.serving.loadgen.LoadSpec`` (steady rate, bursts at a multiple of
it, fixed burst dwell), moved from per-tick draws to continuous time.
"""
from __future__ import annotations

import numpy as np


def derive_seed(seed: int, stream: str) -> np.random.Generator:
    """Independent generator for one named stream of a run's ``--seed``."""
    salt = int.from_bytes(stream.encode(), "little") % (2 ** 63)
    return np.random.default_rng([int(seed) % (2 ** 63), salt])


def mmpp_arrivals(rng: np.random.Generator, n_clients: int, seconds: float,
                  base_hz: float, burst_factor: float,
                  burst_entry_hz: float, burst_dwell_s: float):
    """Per-client MMPP arrivals in [0, seconds).

    A client in the steady state enters a burst at ``burst_entry_hz``; a
    burst lasts ``burst_dwell_s`` at ``burst_factor * base_hz``.  Returns
    (due [M] float64 sorted, client [M] int64)."""
    due, who = [], []
    for c in range(n_clients):
        t = 0.0
        while t < seconds:
            steady = rng.exponential(1.0 / burst_entry_hz) \
                if burst_entry_hz > 0 else seconds
            for t0, t1, hz in ((t, t + steady, base_hz),
                               (t + steady, t + steady + burst_dwell_s,
                                base_hz * burst_factor)):
                t1 = min(t1, seconds)
                if t1 <= t0:
                    continue
                n = rng.poisson(hz * (t1 - t0))
                due.append(rng.uniform(t0, t1, size=n))
                who.append(np.full(n, c, np.int64))
            t += steady + burst_dwell_s
    due = np.concatenate(due) if due else np.zeros(0)
    who = np.concatenate(who) if who else np.zeros(0, np.int64)
    order = np.argsort(due, kind="stable")
    return due[order], who[order]


def mmpp_mean_hz(base_hz: float, burst_factor: float, burst_entry_hz: float,
                 burst_dwell_s: float) -> float:
    """Long-run mean rate of one client of ``mmpp_arrivals``."""
    steady_s = 1.0 / burst_entry_hz if burst_entry_hz > 0 else float("inf")
    burst_share = burst_dwell_s / (steady_s + burst_dwell_s)
    return base_hz * (1.0 + (burst_factor - 1.0) * burst_share)


def periodic_events(rng: np.random.Generator, n_sources: int, hz: float,
                    seconds: float):
    """Each source fires every 1/hz s from a random phase (the mappers'
    keyframes).  Returns (due [M] sorted, source [M])."""
    phase = rng.uniform(0.0, 1.0 / hz, size=n_sources)
    due, src = [], []
    for s in range(n_sources):
        t = np.arange(phase[s], seconds, 1.0 / hz)
        due.append(t)
        src.append(np.full(len(t), s, np.int64))
    due = np.concatenate(due)
    src = np.concatenate(src)
    order = np.argsort(due, kind="stable")
    return due[order], src[order]


def anchors(rng: np.random.Generator, n: int, room_m: float,
            height_m: float = 1.5) -> np.ndarray:
    """[n, 3] walking anchors spread over the inner 80% of the floor."""
    half = 0.8 * room_m / 2
    return np.stack([rng.uniform(-half, half, size=n), np.full(n, height_m),
                     rng.uniform(-half, half, size=n)], axis=1)


def orbit_poses(anchor: np.ndarray, phase: np.ndarray, orbit_m: float,
                walk_m_per_s: float, tau: float) -> np.ndarray:
    """[C, 3] float32 poses at ``tau`` s: each client walks a circle of
    radius ``orbit_m`` around its anchor at ``walk_m_per_s``."""
    ang = phase + (walk_m_per_s / orbit_m) * tau
    off = np.stack([orbit_m * np.cos(ang), np.zeros_like(ang),
                    orbit_m * np.sin(ang)], axis=1)
    return (anchor + off).astype(np.float32)
