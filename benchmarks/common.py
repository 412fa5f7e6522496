"""Shared benchmark plumbing: CSV writing, timing, tiny stats."""

from __future__ import annotations

import csv
import os
import time

import numpy as np

OUT_DIR = os.environ.get("BENCH_OUT", "bench_out")


def write_csv(name: str, rows: list, header: list) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, name)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    print(f"  wrote {path} ({len(rows)} rows)")
    return path


def timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, time.perf_counter() - t0


def rel_stats(rel: np.ndarray) -> tuple:
    return float(np.mean(rel)), float(np.std(rel)), float(np.max(rel))


def banner(title: str):
    print(f"\n=== {title} ===")
