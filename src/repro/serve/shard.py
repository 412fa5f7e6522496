"""Device-sharded solve fan-out: saturate every local device from one call.

``repro.engine.service.solve_bulk`` packs a population into exact arena
buckets and solves each bucket in one vmapped launch — on ONE
device.  This module partitions that bucket list across the local JAX
devices and runs each partition on its own device in its own thread, so a
bulk solve saturates the host instead of leaving all but one accelerator
idle.  The per-bucket machinery is exactly the engine's (`_solve_bucket`,
`_replay_hits` — the hooks service.py exposes): a sharded solve runs the
same float ops in the same order per element, so results are parity-locked
to the single-device path (gated ≤1e-9 in tests; bit-identical on one
device kind).

Assignment is **deterministic** (tests pin it): every bucket gets a work
cost ``B * m * T``; buckets are split in half along the batch axis until
there are at least as many chunks as shards (splitting the costliest
splittable chunk first); the chunks are then LPT-assigned — sorted by
(cost desc, bucket key, batch offset), each placed on the least-loaded
shard, ties toward the lowest shard index.  The same population therefore
lands on the same devices in every process and every run.

Two shard granularities:

* ``devices`` — real ``jax.Device``s; each worker thread enters
  ``jax.default_device(dev)`` so its buckets compile and run there
  (the ``runtime/dlt_runner`` forced-host-device tests show the multi-
  device CPU idiom: ``XLA_FLAGS=--xla_force_host_platform_device_count``).
* ``n_shards`` — logical shards on the default device: the identical
  fan-out/split/merge machinery, thread-parallel host work, one device.
  This is the 1-device degenerate case the bench documents — parity is
  the gate there, scaling is gated when ≥2 real devices exist.
"""

from __future__ import annotations

import dataclasses
import threading
import time

from repro.obs import metrics as obs_metrics
from repro.obs.trace import span

__all__ = ["local_devices", "plan_shards", "solve_bulk_sharded"]


def local_devices() -> list:
    """The local JAX devices (deferred import: serve stays importable
    without pulling jax until a sharded solve actually runs)."""
    import jax

    return list(jax.local_devices())


# ---------------- deterministic bucket -> shard assignment ----------------


def _cost(bucket) -> int:
    """Work proxy for one packed bucket (batch x tableau footprint)."""
    return bucket.B * bucket.m * bucket.T


def _slice_bucket(bucket, lo: int, hi: int):
    """The [lo:hi) batch rows of ``bucket`` as a standalone PackedBucket.

    Only the batch-leading arrays and the member lists slice; the shared
    per-bucket metadata (key, dims, cell maps) is identical by construction,
    so a sliced bucket solves exactly as its rows did in the parent.
    """
    return dataclasses.replace(
        bucket,
        instances=bucket.instances[lo:hi],
        indices=bucket.indices[lo:hi],
        w_cell=bucket.w_cell[lo:hi],
        z=bucket.z[lo:hi],
        latency=bucket.latency[lo:hi],
        tau=bucket.tau[lo:hi],
        vcomm_cell=bucket.vcomm_cell[lo:hi],
        vcomp_cell=bucket.vcomp_cell[lo:hi],
        rel_cell=bucket.rel_cell[lo:hi],
        ret_cell=bucket.ret_cell[lo:hi],
    )


def plan_shards(buckets: list, n_shards: int) -> list:
    """Partition ``buckets`` into ``n_shards`` deterministic work lists.

    Returns a list of ``n_shards`` lists of (possibly batch-sliced)
    ``PackedBucket``s.  See the module docstring for the exact rule; the
    invariants tests pin are (a) every input batch row appears in exactly
    one output chunk, (b) the assignment is a pure function of the bucket
    keys/sizes and ``n_shards``, and (c) no chunk is ever empty while a
    shard with work for it exists.
    """
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    # chunks: (key, lo, bucket) — lo is the batch offset within the parent
    chunks = [(b.key, 0, b) for b in sorted(buckets, key=lambda b: b.key)]
    if n_shards > 1:
        # split the costliest splittable chunk in half until there are
        # enough chunks to feed every shard (or nothing can split further)
        while len(chunks) < n_shards:
            splittable = [i for i, c in enumerate(chunks) if c[2].B >= 2]
            if not splittable:
                break
            at = max(splittable,
                     key=lambda i: (_cost(chunks[i][2]), chunks[i][0],
                                    -chunks[i][1]))
            key, lo, big = chunks.pop(at)
            mid = big.B // 2
            chunks.append((key, lo, _slice_bucket(big, 0, mid)))
            chunks.append((key, lo + mid, _slice_bucket(big, mid, big.B)))
    # LPT assignment: costliest first onto the least-loaded shard
    chunks.sort(key=lambda c: (-_cost(c[2]), c[0], c[1]))
    loads = [0] * n_shards
    shards: list = [[] for _ in range(n_shards)]
    for key, lo, chunk in chunks:
        i = min(range(n_shards), key=lambda j: (loads[j], j))
        shards[i].append(chunk)
        loads[i] += _cost(chunk)
    return shards


# ---------------- the sharded bulk solve ----------------


def solve_bulk_sharded(
    instances: list,
    objective: str = "makespan",
    cache=None,
    fallback: bool = True,
    validate: bool = True,
    warm_starts: list | None = None,
    devices: list | None = None,
    n_shards: int | None = None,
) -> list:
    """``solve_bulk`` with the arena buckets fanned out across devices.

    ``devices`` pins explicit JAX devices (default: every local device);
    ``n_shards`` instead runs that many logical shards on the default
    device (thread fan-out only — the 1-device degenerate case).  With one
    shard total this IS ``solve_bulk`` (same code path, no threads).
    Results are in caller order and parity-locked to the single-device
    path; the shared solution cache and the metrics registry are both
    thread-safe, so shards write concurrently without coordination.
    """
    from repro.engine.service import (_LABEL, _replay_hits, _solve_bucket,
                                      solve_bulk)

    if devices is not None and n_shards is not None:
        if len(devices) != n_shards:
            raise ValueError(
                f"devices ({len(devices)}) and n_shards ({n_shards}) disagree")
    if devices is None and n_shards is not None:
        shard_devices: list = [None] * n_shards  # logical shards, one device
    else:
        shard_devices = list(devices) if devices is not None else local_devices()
    n_dev = len(shard_devices)
    if n_dev < 1:
        raise ValueError("need at least one device/shard")
    if n_dev == 1 or objective != "makespan":
        return solve_bulk(
            instances, objective=objective, cache=cache, fallback=fallback,
            validate=validate, warm_starts=warm_starts,
        )

    from repro.engine.arena import InstanceArena

    met = obs_metrics.get_registry()
    met.inc("repro_engine_bulk_solves_total", path=_LABEL)
    met.inc("repro_serve_sharded_solves_total", shards=n_dev)
    with span("serve.shard_solve", n=len(instances), shards=n_dev, path=_LABEL):
        n = len(instances)
        results: list = [None] * n
        t0 = time.perf_counter()
        with span("engine.cache_lookup", n=n):
            if cache is not None:
                keys = cache.keys(instances, objective)
                sols = cache.lookup_many(keys)
            else:
                keys = [None] * n
                sols = [None] * n
            pending = [i for i, sol in enumerate(sols) if sol is None]
            hit_idx = [i for i in range(n) if sols[i] is not None]
        cache_s = time.perf_counter() - t0
        if hit_idx:
            _replay_hits(instances, hit_idx, sols, results, cache_s, met)
        if not pending:
            return results

        t0 = time.perf_counter()
        with span("engine.pack", n=len(pending)):
            arena = InstanceArena(
                [instances[i] for i in pending], pad_shapes=False)
        pack_s = time.perf_counter() - t0
        shards = plan_shards(arena.buckets, n_dev)
        shared_stages = {"cache_lookup_s": cache_s, "pack_s": pack_s}

        errors: list = [None] * n_dev

        def worker(i: int) -> None:
            dev = shard_devices[i]
            buckets = shards[i]
            elems = sum(b.B for b in buckets)
            dev_label = str(dev) if dev is not None else f"logical:{i}"
            t_dev = time.perf_counter()
            try:
                with span("serve.shard", shard=i, device=dev_label,
                          n_buckets=len(buckets), n=elems):
                    ctx = _device_ctx(dev)
                    with ctx:
                        for bucket in buckets:
                            _solve_bucket(
                                bucket, instances, results, keys, pending,
                                cache, fallback, validate,
                                met, shared_stages, warm_starts)
            except BaseException as e:  # surfaced after join, first wins
                errors[i] = e
            finally:
                met.observe("repro_serve_shard_seconds",
                            time.perf_counter() - t_dev,
                            shard=i, path=_LABEL)
                met.inc("repro_serve_shard_elements_total", elems, shard=i)

        threads = [
            threading.Thread(target=worker, args=(i,),
                             name=f"serve-shard-{i}", daemon=True)
            for i in range(n_dev)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for e in errors:
            if e is not None:
                raise e
    return results


def _device_ctx(dev):
    """``jax.default_device(dev)`` for a real device, no-op for a logical
    shard (None)."""
    if dev is None:
        import contextlib

        return contextlib.nullcontext()
    import jax

    return jax.default_device(dev)
