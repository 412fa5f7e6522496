"""repro.engine — the batched, JAX-native scheduling engine.

Evaluates and solves whole populations of paper instances in parallel:

* :mod:`repro.engine.arena` — packs heterogeneous instances into fixed-shape
  padded batches bucketed by ``(topology, has_returns, m, T, q)``;
* :mod:`repro.engine.batched_sim` — the topology-dispatched ASAP recurrence
  (chain store-and-forward or star one-port master, plus the optional
  result-return phase) as a ``lax.scan``, jitted and ``vmap``-ed
  (bit-matches the NumPy simulator);
* :mod:`repro.engine.batched_simplex` — a fixed-shape two-phase dense
  simplex under ``vmap`` for thousands of small schedule LPs at once;
* :mod:`repro.engine.cache` / :mod:`repro.engine.service` — quantized
  instance hashing, solution caching, and the submit/flush bulk front-end.

Serial reference implementations live in :mod:`repro.core`; everything here
is cross-checked against them (tests/test_engine_parity.py).
"""

from .arena import InstanceArena, PackedBucket, pack_instances
from .batched_sim import makespans, simulate_bucket, simulate_many
from .batched_simplex import STATUS, BatchedSimplexResult, solve_simplex_batched
from .cache import CachedSolution, SolutionCache, instance_key
from .service import BatchedBackend, PlanService, solve_bulk

__all__ = [
    "InstanceArena",
    "PackedBucket",
    "pack_instances",
    "BatchedBackend",
    "simulate_bucket",
    "simulate_many",
    "makespans",
    "BatchedSimplexResult",
    "solve_simplex_batched",
    "STATUS",
    "SolutionCache",
    "CachedSolution",
    "instance_key",
    "PlanService",
    "solve_bulk",
]
