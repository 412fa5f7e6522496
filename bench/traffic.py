"""The one traffic generator: a configuration file and a traffic-mix file in,
the requests of a run out, all drawn from the seed.

A configuration (``configs/<name>.json``) fixes the deployment: platform
family and size, loads per request, and the distributions of the paper's
section-6 protocol (processing powers, link speeds with latencies that fall
as bandwidth rises, compute volumes, communication-to-computation ratios).
A traffic mix (``traffic/<name>.json``) fixes how clients send: how many
closed-loop clients, and the share of answers the comparison checks in full.

Each request is drawn on its own from ``(seed, client, k)``, so the load
generator and the comparison make the same problem without passing it
around, and a client can send as many as the window has room for.  Every
seed gets the same set of communication-to-computation ratios: each client
walks the ratio grid in a fresh seed-drawn order, one whole grid per
``len(grid)`` requests, so seeds differ in the order and the instances and
not in how much work the ratios make.
"""

from __future__ import annotations

import json
import os

import numpy as np

__all__ = ["BENCH", "load_config", "load_mix", "draw_problem", "request",
           "checked"]

BENCH = os.path.dirname(os.path.abspath(__file__))


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _load("configs", name)


def load_mix(name: str) -> dict:
    return _load("traffic", name)


def draw_problem(rng: np.random.Generator, cfg: dict, ratio: float) -> dict:
    """One platform and its loads under the section-6 protocol: powers and
    link speeds uniform in their ranges, per-message latency falling
    linearly from its maximum on the slowest link to its minimum on the
    fastest, compute volumes uniform, data volume = ratio * compute volume."""
    m, n = cfg["m"], cfg["n_loads"]
    lo, hi = cfg["power_flop_per_s"]
    power = rng.uniform(lo, hi, size=m)
    lo, hi = cfg["link_bit_per_s"]
    bw = rng.uniform(lo / 8, hi / 8, size=m - 1)  # bytes/s
    lat_min, lat_max = cfg["latency_s"]
    frac = (bw - bw.min()) / max(float(np.ptp(bw)), 1e-30)
    lo, hi = cfg["v_comp_flop"]
    v_comp = rng.uniform(lo, hi, size=n)
    return {
        "topology": cfg["topology"],
        "w": (1.0 / power).tolist(),
        "z": (1.0 / bw).tolist(),
        "tau": [0.0] * m,
        "latency": ((1.0 - frac) * (lat_max - lat_min) + lat_min).tolist(),
        "v_comm": (v_comp * ratio).tolist(),
        "v_comp": v_comp.tolist(),
        "release": [0.0] * n,
        "return_ratio": [float(cfg["return_ratio"])] * n,
    }


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, *key])


def request(cfg: dict, seed: int, client: int, k: int) -> dict:
    """The problem of client ``client``'s ``k``-th request."""
    grid = cfg["comm_to_comp"]
    block, pos = divmod(k, len(grid))
    order = _rng(seed, client, block, 0).permutation(len(grid))
    return draw_problem(_rng(seed, client, k, 1), cfg, grid[order[pos]])


def checked(mix: dict, seed: int, client: int, k: int) -> bool:
    """Whether the comparison checks that request's answer in full."""
    return bool(_rng(seed, client, k, 2).random() < mix["check_share"])
