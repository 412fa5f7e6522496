"""The engine's two jitted programs compile for one TPU v5e at smoke size.

``_solve_batch`` (the batched two-phase simplex) and ``_sim_batch`` (the
ASAP replay, chain and star-with-returns) are compiled under x64 for a
described ``v5e:2x2`` chip — no chip needed — at the shapes
``chip_smoke.py`` serves: a bucket of B=16 Table-2 chains (m=10,
heterogeneous, latencies, 50 loads, q=1) and of 16 stars with result
return (m=10, 10 loads).  What the TPU compiler refuses, or a program that
would not fit the chip's 16 GB of HBM, fails here at no chip time.

The topology is described inside a module fixture only, never at import:
one process at a time may load the TPU library, so the worker that runs
this file loads it and the others never try.
"""

import os

import numpy as np
import pytest

B = 16  # requests per population in chip_smoke.py: one bucket each
HBM_BYTES = 16e9  # one TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(os.environ, "TPU_LOG_DIR",
                   os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _bucket(topology, n_loads, return_ratio):
    from repro.core.instance import random_instance
    from repro.engine.arena import InstanceArena

    inst = random_instance(
        np.random.default_rng(0), m=10, n_loads=n_loads, heterogeneous=True,
        with_latency=True, topology=topology, return_ratio=return_ratio)
    return InstanceArena([inst]).buckets[0]


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one v5e"
    return total


def test_solve_batch_compiles_for_v5e_at_table2_size(one_chip):
    import jax
    import jax.numpy as jnp

    from repro.engine.batched_lp import build_lp_bucket
    from repro.engine.batched_simplex import _packed_lp_struct, _solve_batch
    from repro.jaxenv import x64

    lp = build_lp_bucket(_bucket("chain", 50, 0.0))
    n, mu, me = lp.c.shape[0], lp.A_ub.shape[1], lp.A_eq.shape[1]
    tableau = B * (mu + me + 1) * (n + mu + 2) * 8
    assert tableau > 1e9  # the bucket is real work: ~1 GB of f64 tableau
    with x64():
        compiled = _solve_batch.lower(
            _packed_lp_struct(B, n, mu, me, sharding=one_chip), n, mu, me,
            20_000,
        ).compile()
    assert _fits(compiled) > tableau
    (out,) = jax.tree.leaves(compiled.out_info)  # one packed result
    assert out.shape == (B, n + 5 + mu + me) and out.dtype == jnp.float64


@pytest.mark.parametrize("topology,n_loads,return_ratio", [
    ("chain", 50, 0.0),
    ("star", 10, 0.5),
])
def test_sim_batch_compiles_for_v5e(one_chip, topology, n_loads, return_ratio):
    import jax
    import jax.numpy as jnp

    from repro.engine.batched_sim import _sim_batch
    from repro.jaxenv import x64

    bk = _bucket(topology, n_loads, return_ratio)
    m, T = bk.m, bk.T
    with_ret = bool(bk.has_returns)
    assert with_ret == (return_ratio > 0)
    with x64():
        S = lambda *s: jax.ShapeDtypeStruct(s, jnp.float64, sharding=one_chip)
        compiled = _sim_batch.lower(
            S(B, m, T), S(B, m - 1), S(B, m - 1), S(B, m), S(B, T), S(B, T),
            S(B, T), S(B, T), S(T), S(B, m, T), topology, with_ret,
        ).compile()
    _fits(compiled)
    out = compiled.out_info  # every output comes back float64
    assert all(o.dtype == jnp.float64 for o in jax.tree.leaves(out))
