"""Device-sharded solve fan-out: deterministic bucket->shard assignment,
row coverage under batch slicing, and gamma parity (<= 1e-9) between the
sharded and single-device bulk paths — including through the engine hook
(``solve_bulk(n_shards=...)``) and on real (forced-host) multi-device JAX
in a subprocess.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.instance import random_instance
from repro.engine.arena import InstanceArena
from repro.engine.cache import SolutionCache
from repro.engine.service import solve_bulk
from repro.serve import plan_shards, solve_bulk_sharded


def _population(n: int = 24, seed: int = 5) -> list:
    # three distinct shapes -> three arena buckets with different costs
    rng = np.random.default_rng(seed)
    out = []
    for k in range(n):
        m = 2 + (k % 3)
        out.append(random_instance(rng, m=m, n_loads=1 + (k % 2), q=2))
    return out


def _buckets(insts: list) -> list:
    return InstanceArena(insts, pad_shapes=False).buckets


def _flatten(shards: list) -> list:
    return [(c.key, tuple(c.indices)) for shard in shards for c in shard]


# ---------------- assignment planning ----------------


def test_plan_shards_is_deterministic():
    insts = _population()
    a = plan_shards(_buckets(insts), 3)
    b = plan_shards(_buckets(insts), 3)
    assert _flatten(a) == _flatten(b)
    assert [len(s) for s in a] == [len(s) for s in b]


def test_plan_shards_covers_every_row_exactly_once():
    insts = _population()
    buckets = _buckets(insts)
    want = sorted((b.key, i) for b in buckets for i in b.indices)
    for n_shards in (1, 2, 3, 5):
        shards = plan_shards(buckets, n_shards)
        got = sorted((c.key, i) for shard in shards for c in shard
                     for i in c.indices)
        assert got == want, f"n_shards={n_shards} lost or duplicated rows"


def test_plan_shards_splits_one_big_bucket():
    rng = np.random.default_rng(0)
    insts = [random_instance(rng, m=3, n_loads=2, q=2) for _ in range(8)]
    (bucket,) = _buckets(insts)
    shards = plan_shards([bucket], 2)
    assert all(shard for shard in shards)  # both shards got work
    sizes = sorted(sum(c.B for c in shard) for shard in shards)
    assert sizes == [4, 4]  # halved along the batch axis


def test_plan_shards_single_instance_cannot_split():
    rng = np.random.default_rng(0)
    (bucket,) = _buckets([random_instance(rng, m=3, n_loads=1, q=1)])
    shards = plan_shards([bucket], 4)
    assert sum(len(s) for s in shards) == 1  # B=1 is indivisible
    assert len(shards) == 4


def test_plan_shards_rejects_bad_count():
    with pytest.raises(ValueError, match="n_shards"):
        plan_shards([], 0)


def test_sliced_bucket_solves_like_its_parent_rows():
    # a batch slice must carry its rows' coefficients verbatim
    rng = np.random.default_rng(3)
    insts = [random_instance(rng, m=3, n_loads=2, q=2) for _ in range(6)]
    (bucket,) = _buckets(insts)
    shards = plan_shards([bucket], 2)
    for shard in shards:
        for chunk in shard:
            rows = [list(bucket.indices).index(i) for i in chunk.indices]
            np.testing.assert_array_equal(chunk.w_cell,
                                          bucket.w_cell[rows])
            np.testing.assert_array_equal(chunk.z, bucket.z[rows])
            assert chunk.key == bucket.key
            assert chunk.m == bucket.m and chunk.T == bucket.T


# ---------------- parity with the single-device path ----------------


def test_sharded_parity_logical_shards():
    insts = _population()
    single = solve_bulk(insts)
    for n_shards in (2, 3):
        sharded = solve_bulk_sharded(insts, n_shards=n_shards)
        for r1, r2 in zip(single, sharded):
            assert r2.ok and r2.backend == r1.backend
            np.testing.assert_allclose(r2.schedule.gamma, r1.schedule.gamma,
                                       atol=1e-9, rtol=0)
            assert r2.lp_makespan == pytest.approx(r1.lp_makespan, abs=1e-9)


def test_sharded_parity_with_shared_cache():
    insts = _population(n=12, seed=9)
    cache = SolutionCache()
    first = solve_bulk_sharded(insts, n_shards=2, cache=cache)
    assert all(r.ok for r in first)
    assert len(cache) > 0
    # every slot is now a hit; the sharded path replays them identically
    hits_before = cache.hits
    again = solve_bulk_sharded(insts, n_shards=2, cache=cache)
    assert cache.hits == hits_before + len(insts)
    for r1, r2 in zip(first, again):
        np.testing.assert_allclose(r2.schedule.gamma, r1.schedule.gamma,
                                   atol=1e-9, rtol=0)


def test_sharded_single_shard_is_solve_bulk():
    insts = _population(n=6)
    a = solve_bulk(insts)
    b = solve_bulk_sharded(insts, n_shards=1)
    for r1, r2 in zip(a, b):
        np.testing.assert_array_equal(r2.schedule.gamma, r1.schedule.gamma)


def test_sharded_rejects_disagreeing_device_args():
    with pytest.raises(ValueError, match="disagree"):
        solve_bulk_sharded(_population(n=2), devices=[None], n_shards=3)


def test_engine_hook_solve_bulk_n_shards():
    # the service-layer entry: solve_bulk itself fans out when asked
    insts = _population(n=12, seed=11)
    single = solve_bulk(insts)
    sharded = solve_bulk(insts, n_shards=2)
    for r1, r2 in zip(single, sharded):
        assert r2.ok
        np.testing.assert_allclose(r2.schedule.gamma, r1.schedule.gamma,
                                   atol=1e-9, rtol=0)


# ---------------- real multi-device (forced host devices) ----------------


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np
from repro.core.instance import random_instance
from repro.engine.service import solve_bulk
from repro.serve import local_devices, solve_bulk_sharded

devices = local_devices()
assert len(devices) == 2, devices
rng = np.random.default_rng(5)
insts = [random_instance(rng, m=2 + (k % 2), n_loads=1, q=1)
         for k in range(6)]
single = solve_bulk(insts)
sharded = solve_bulk_sharded(insts, devices=devices)
diff = max(float(np.max(np.abs(a.schedule.gamma - b.schedule.gamma)))
           for a, b in zip(single, sharded))
assert diff <= 1e-9, diff
assert all(r.ok for r in sharded)
print("parity", diff)
"""


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("REPRO_SLOW") != "1",
                    reason="~8 min on a 1-core box: the subprocess pays jax "
                           "import + per-device XLA compiles; the logical-"
                           "shard parity tests above gate the same math. "
                           "Set REPRO_SLOW=1 to run the real-device path.")
def test_sharded_parity_two_real_devices():
    # smoke tests elsewhere must keep seeing 1 device, so the forced-host
    # multi-device run happens in a subprocess (the dlt_runner idiom)
    # forced host devices exist only on the CPU backend
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "parity" in proc.stdout
