"""Persistent plan store robustness: concurrent writers (threads AND
processes), corruption/truncation recovery, schema-version skew, TTL + LRU
bounds, and the tiered-cache invariant that a store hit produces a
``diff()``-clean artifact against a fresh solve.
"""

import json
import os
import sqlite3
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.api import Policy, Problem, Session
from repro.engine.cache import CachedSolution
from repro.serve import STORE_SCHEMA_VERSION, PlanStore, TieredSolutionCache


def _sol(v: float = 1.0) -> CachedSolution:
    return CachedSolution(gamma=np.full((2, 2), v), lp_makespan=v,
                          backend="batched")


def _problem(scale: float = 1.0) -> Problem:
    return Problem(w=[1.0, 2.0 * scale], z=[0.1], v_comm=[1.0],
                   v_comp=[3.0 * scale])


# ---------------- basics ----------------


def test_store_roundtrip_and_stats(tmp_path):
    with PlanStore(tmp_path / "p.sqlite") as st:
        assert st.get("k0") is None
        st.put("k0", _sol(2.0))
        got = st.get("k0")
        np.testing.assert_array_equal(got.gamma, np.full((2, 2), 2.0))
        assert got.lp_makespan == 2.0 and got.backend == "batched"
        assert len(st) == 1
        s = st.stats()
        assert s["hits"] == 1 and s["misses"] == 1 and s["entries"] == 1
        assert s["quarantines"] == 0


def test_store_survives_reopen(tmp_path):
    path = tmp_path / "p.sqlite"
    with PlanStore(path) as st:
        st.put("k0", _sol(3.0))
    with PlanStore(path) as st2:  # the "second process"
        assert st2.get("k0").lp_makespan == 3.0


def test_store_lookup_many_mixed(tmp_path):
    with PlanStore(tmp_path / "p.sqlite") as st:
        st.put("a", _sol(1.0))
        st.put("c", _sol(3.0))
        sols = st.lookup_many(["a", "b", "c"])
        assert sols[0].lp_makespan == 1.0 and sols[1] is None
        assert sols[2].lp_makespan == 3.0
        assert st.hits == 2 and st.misses == 1


def test_store_ttl_expiry(tmp_path):
    clk = [0.0]
    with PlanStore(tmp_path / "p.sqlite", ttl_s=10.0,
                   clock=lambda: clk[0]) as st:
        st.put("k", _sol())
        clk[0] = 5.0
        assert st.get("k") is not None
        clk[0] = 20.0
        assert st.get("k") is None  # expired rows read as a miss and delete
        assert st.expirations == 1 and len(st) == 0
        st.put("k2", _sol())
        clk[0] = 40.0
        assert st.sweep_expired() == 1
        assert len(st) == 0


def test_store_lru_eviction_over_restarts(tmp_path):
    clk = [0.0]
    with PlanStore(tmp_path / "p.sqlite", max_entries=3,
                   clock=lambda: clk[0]) as st:
        for i in range(3):
            clk[0] += 1
            st.put(f"k{i}", _sol(float(i)))
        clk[0] += 1
        st.get("k0")  # touch: k0 becomes most recent, k1 is now LRU
        clk[0] += 1
        st.put("k3", _sol(3.0))
        assert st.evictions == 1
        assert st.get("k1") is None  # the LRU row went
        assert st.get("k0") is not None and st.get("k3") is not None


# ---------------- concurrency ----------------


def test_store_thread_hammer_8_threads(tmp_path):
    # >= 8 threads share ONE store: no write may be lost to a race, no read
    # may crash, and the hit/miss counters must exactly cover the lookups
    st = PlanStore(tmp_path / "p.sqlite", max_entries=4096)
    n_threads, per_thread = 8, 50
    barrier = threading.Barrier(n_threads)
    errors: list = []

    def worker(tid):
        try:
            barrier.wait()
            for k in range(per_thread):
                key = f"t{tid}-{k}"
                st.put(key, _sol(float(tid * 1000 + k)))
                got = st.get(key)
                assert got is not None, key  # own write always visible
                assert got.lp_makespan == float(tid * 1000 + k)
                st.lookup_many([f"t{(tid + 1) % n_threads}-{k}", "absent"])
        except BaseException as e:  # pragma: no cover - the assertion target
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert len(st) == n_threads * per_thread
    assert st.quarantines == 0 and st.corrupt_rows == 0
    lookups = n_threads * per_thread * 3  # get + 2-key lookup_many each
    assert st.hits + st.misses == lookups
    st.close()


def test_store_two_process_hammer(tmp_path):
    # a sibling process writes the same file while this one does: sqlite's
    # transaction atomicity must leave every row from both sides readable
    path = tmp_path / "p.sqlite"
    n = 40
    script = (
        "import sys, numpy as np\n"
        "from repro.serve import PlanStore\n"
        "from repro.engine.cache import CachedSolution\n"
        "st = PlanStore(sys.argv[1])\n"
        f"for i in range({n}):\n"
        "    st.put(f'proc-b-{i}', CachedSolution(gamma=np.full((2, 2), float(i)),"
        " lp_makespan=float(i), backend='batched'))\n"
        "st.close()\n"
        "print('done')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    proc = subprocess.Popen([sys.executable, "-c", script, str(path)],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    st = PlanStore(path)
    for i in range(n):
        st.put(f"proc-a-{i}", _sol(float(i)))
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err
    assert "done" in out
    assert len(st) == 2 * n
    for i in range(n):
        assert st.get(f"proc-a-{i}").lp_makespan == float(i)
        assert st.get(f"proc-b-{i}").lp_makespan == float(i)
    assert st.quarantines == 0
    st.close()


# ---------------- corruption: never crash ----------------


def test_store_truncated_file_quarantines(tmp_path):
    path = tmp_path / "p.sqlite"
    with PlanStore(path) as st:
        st.put("k", _sol())
    with open(path, "r+b") as f:  # tear the header off
        f.truncate(7)
    st2 = PlanStore(path)  # must not raise
    assert st2.quarantines == 1
    assert st2.get("k") is None  # fresh store: the torn data is gone...
    st2.put("k2", _sol())
    assert st2.get("k2") is not None  # ...and the path serves again
    assert os.path.exists(str(path) + ".quarantined-0")  # evidence kept
    st2.close()


def test_store_garbage_file_quarantines(tmp_path):
    path = tmp_path / "p.sqlite"
    path.write_bytes(b"this is not a sqlite database at all--------")
    st = PlanStore(path)
    assert st.quarantines == 1 and len(st) == 0
    st.put("k", _sol())
    assert st.get("k") is not None
    st.close()


def test_store_corrupt_row_reads_as_miss(tmp_path):
    path = tmp_path / "p.sqlite"
    with PlanStore(path) as st:
        st.put("good", _sol(1.0))
        st.put("bad", _sol(2.0))
    con = sqlite3.connect(path)
    con.execute("UPDATE plans SET payload='{not json' WHERE key='bad'")
    con.commit()
    con.close()
    with PlanStore(path) as st2:
        assert st2.get("bad") is None  # deleted + counted, not raised
        assert st2.corrupt_rows == 1
        assert st2.get("good").lp_makespan == 1.0  # neighbours unharmed
        assert len(st2) == 1


def test_store_quarantine_names_never_collide(tmp_path):
    path = tmp_path / "p.sqlite"
    for expected in range(2):
        path.write_bytes(b"garbage-" * 8)
        st = PlanStore(path)
        st.close()
        assert os.path.exists(f"{path}.quarantined-{expected}")


# ---------------- schema-version skew ----------------


def test_store_newer_schema_quarantines(tmp_path):
    path = tmp_path / "p.sqlite"
    with PlanStore(path) as st:
        st.put("k", _sol())
    con = sqlite3.connect(path)
    con.execute("UPDATE meta SET value=? WHERE key='schema_version'",
                (str(STORE_SCHEMA_VERSION + 1),))
    con.commit()
    con.close()
    st2 = PlanStore(path)  # a future store: refuse to guess, quarantine
    assert st2.quarantines == 1
    assert st2.get("k") is None
    st2.put("k", _sol(5.0))
    assert st2.get("k").lp_makespan == 5.0
    st2.close()


def test_store_older_schema_migrates_in_place(tmp_path):
    path = tmp_path / "p.sqlite"
    with PlanStore(path) as st:
        pass  # create the schema
    con = sqlite3.connect(path)
    con.execute("UPDATE meta SET value='0' WHERE key='schema_version'")
    payload = json.dumps({"g": [[0.25, 0.75], [0.5, 0.5]], "mk": 4.0})
    con.execute(
        "INSERT INTO plans (key, schema, payload, created, last_access) "
        "VALUES ('old', 0, ?, 1.0, 1.0)", (payload,))
    con.commit()
    con.close()
    with PlanStore(path) as st2:  # no quarantine: migrate
        assert st2.quarantines == 0
        got = st2.get("old")  # row upgrades lazily on read
        np.testing.assert_array_equal(
            got.gamma, np.asarray([[0.25, 0.75], [0.5, 0.5]]))
        assert got.lp_makespan == 4.0 and got.backend == "unknown"
    con = sqlite3.connect(path)
    stamp = con.execute(
        "SELECT value FROM meta WHERE key='schema_version'").fetchone()[0]
    con.close()
    assert int(stamp) == STORE_SCHEMA_VERSION  # store stamp bumped now


def test_store_unknown_old_record_is_corrupt_not_crash(tmp_path):
    path = tmp_path / "p.sqlite"
    with PlanStore(path) as st:
        pass
    con = sqlite3.connect(path)
    con.execute(
        "INSERT INTO plans (key, schema, payload, created, last_access) "
        "VALUES ('weird', 99, ?, 1.0, 1.0)",
        (json.dumps({"schema": 99, "mystery": True}),))
    con.commit()
    con.close()
    with PlanStore(path) as st2:
        assert st2.get("weird") is None
        assert st2.corrupt_rows == 1


# ---------------- the tiered cache ----------------


def test_tiered_cache_promotes_and_writes_through(tmp_path):
    path = tmp_path / "p.sqlite"
    a = TieredSolutionCache(path)
    a.put("k", _sol(7.0))
    assert len(a) == 1 and len(a.store) == 1  # write-through
    b = TieredSolutionCache(a.store)  # cold memory, shared disk
    got = b.get("k")
    assert got is not None and got.lp_makespan == 7.0
    assert b.store_hits == 1
    assert b.misses == 0  # a store hit is not a cache miss
    b.store.hits, b.store.misses = 0, 0
    assert b.get("k") is not None
    assert b.store.hits == 0  # second read served from promoted memory
    assert b.hits >= 1


def test_tiered_cache_validation_and_stats(tmp_path):
    c = TieredSolutionCache(tmp_path / "p.sqlite")
    assert c.get("absent") is None
    c.put("k", _sol())
    s = c.stats()
    assert s["store_hits"] == 0 and s["store"]["entries"] == 1
    assert c.evictions == 0


def test_session_store_hit_artifact_diffs_clean(tmp_path):
    # THE serving invariant: an artifact replayed from a store row must be
    # indistinguishable (diff() == {}) from a fresh solve of the same spec
    path = str(tmp_path / "plans.sqlite")
    policy = Policy(installments=2, backend="batched")
    problems = [_problem(1.0 + 0.1 * k) for k in range(4)]

    first = Session(policy, store=path)
    arts1 = [first.solve(p) for p in problems]
    assert all(a.ok and not a.cache_hit for a in arts1)

    second = Session(policy, store=path)  # the restarted "process"
    arts2 = [second.solve(p) for p in problems]
    assert all(a.cache_hit for a in arts2)
    assert second.cache.store_hits == len(problems)

    fresh = Session(policy)  # no store at all: ground truth
    for a2, p in zip(arts2, problems):
        ref = fresh.solve(p)
        assert a2.diff(ref) == {}
        assert a2.makespan == pytest.approx(ref.makespan, abs=1e-12)


def test_session_rejects_cache_and_store_together(tmp_path):
    from repro.engine.cache import SolutionCache

    with pytest.raises(ValueError, match="either cache= or store="):
        Session(Policy(), cache=SolutionCache(),
                store=str(tmp_path / "p.sqlite"))
