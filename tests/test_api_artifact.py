"""PlanArtifact: versioning, JSON round-trip bit-stability, replay, diff.

The acceptance bar: ``to_json``/``from_json`` round-trips bit-identically
across all three backend families on chain AND star instances, with and
without the result-return phase — an artifact written by one process is
byte-for-byte reproducible by another.
"""

import dataclasses

import numpy as np
import pytest

from repro.api import ARTIFACT_VERSION, PlanArtifact, Policy, Problem, Session


def _problem(topology="chain", return_ratio=0.0):
    return Problem(
        w=[1.0, 2.0, 1.5],
        z=[0.3, 0.2],
        v_comm=[1.0, 2.0],
        v_comp=[1.0, 1.5],
        latency=[1e-3, 2e-3],
        release=[0.0, 0.05],
        topology=topology,
        return_ratio=return_ratio,
    )


@pytest.mark.parametrize("backend", ["simplex", "scipy", "batched", "auto"])
@pytest.mark.parametrize(
    "topology,ret", [("chain", 0.0), ("chain", 0.25), ("star", 0.0), ("star", 0.25)]
)
def test_json_round_trip_bit_identical(backend, topology, ret):
    sess = Session()
    art = sess.solve(_problem(topology, ret), Policy(installments=2, backend=backend))
    assert art.ok, (backend, topology, ret, art.status)
    s = art.to_json()
    art2 = PlanArtifact.from_json(s)
    assert art2.to_json() == s  # bit-identical re-serialization
    np.testing.assert_array_equal(art.gamma, art2.gamma)  # exact, not approx
    assert art2.problem == art.problem and art2.policy == art.policy
    assert art2.q == art.q and art2.backend == art.backend
    # a deserialized artifact replays to the identical executable schedule
    sched = art2.schedule()
    assert sched.makespan == pytest.approx(art.makespan, abs=1e-12)
    np.testing.assert_array_equal(sched.gamma, art.gamma)


def test_auto_t_sweep_survives_round_trip():
    sess = Session()
    art = sess.solve(
        _problem(), Policy(auto_t=True, t_max=3, installment_cost=1e-3,
                           backend="simplex")
    )
    assert art.t_star is not None and art.sweep is not None
    s = art.to_json()
    art2 = PlanArtifact.from_json(s)
    assert art2.to_json() == s
    assert art2.t_star == art.t_star
    assert art2.sweep == art.sweep


def test_version_gating():
    sess = Session()
    art = sess.solve(_problem(), Policy(backend="simplex"))
    d = art.to_dict()
    d["version"] = ARTIFACT_VERSION + 1
    with pytest.raises(ValueError, match="version"):
        PlanArtifact.from_dict(d)
    with pytest.raises(ValueError, match="version"):
        PlanArtifact.from_dict({k: v for k, v in d.items() if k != "version"})


def test_diff_flags_decision_changes():
    sess = Session()
    a = sess.solve(_problem(), Policy(installments=2, backend="simplex"))
    b = sess.solve(_problem(), Policy(installments=2, backend="simplex"))
    assert a.diff(b) == {}  # identical solves differ nowhere
    c = sess.solve(_problem(), Policy(installments=1, backend="simplex"))
    d = a.diff(c)
    assert "q" in d and "gamma" in d and "makespan" in d
    # tolerance absorbs sub-tolerance float noise
    shifted = dataclasses.replace(b, makespan=b.makespan + 1e-12)
    assert a.diff(shifted, tol=1e-9) == {}
    assert "makespan" in a.diff(shifted)


def test_provenance_fields():
    sess = Session()
    pol = Policy(installments=2, backend="batched")
    p = _problem()
    first = sess.solve(p, pol)
    again = sess.solve(p, pol)
    assert not first.cache_hit and again.cache_hit
    assert again.backend == "batched+cache"
    assert first.fallback_events == ()
    # cross_check is a serial-only contract: the engine hands it to the
    # serial path, and the artifact records the change of hands
    checked = sess.solve(p, Policy(installments=2, backend="batched",
                                   cross_check=True))
    assert checked.ok and checked.fallback_events
    assert checked.fallback_events[0].startswith("served_by:")


def test_diff_v1_vs_v2_round_trip_regression():
    # the version seam: a v1 document (no events/telemetry keys) diffed
    # against a live v2 artifact must neither crash nor mis-report.  Build
    # the v1 document the way old processes did — serialize, strip the v2
    # keys, mark version 1 — and round-trip it first.
    sess = Session()
    pol = Policy(installments=2, backend="batched")
    v2 = sess.solve(_problem(), pol)
    assert v2.version == ARTIFACT_VERSION and v2.telemetry is not None
    d = v2.to_dict()
    for k in ("events", "telemetry"):
        d.pop(k, None)
    d["version"] = 1
    v1 = PlanArtifact.from_dict(d)
    assert v1.version == 1 and v1.telemetry is None and v1.events == ()
    # v1 round-trips bit-stably without growing v2 keys
    s = v1.to_json()
    assert PlanArtifact.from_json(s).to_json() == s
    assert '"telemetry"' not in s and '"events"' not in s
    # decision diff: same plan, both directions, with and without provenance
    assert v1.diff(v2) == {}
    assert v2.diff(v1) == {}
    pd = v2.diff(v1, include_provenance=True)
    assert pd.get("version") == (2, 1)  # the seam is reported, not silenced
    assert "events" not in pd  # v1's absent events are never compared
    # two v2 artifacts DO compare events under provenance
    replanned = dataclasses.replace(
        v2, events=v2.events + ({"kind": "replan", "trigger": "SpeedObserved"},))
    assert "events" in v2.diff(replanned, include_provenance=True)
    assert v2.diff(replanned) == {}  # decision untouched


def test_diff_nan_gamma_mismatch_is_reported():
    # regression: a failed plan (all-NaN gamma) used to diff CLEAN against a
    # solved one — NaN differences were zeroed by nan_to_num
    sess = Session()
    ok = sess.solve(_problem(), Policy(installments=2, backend="simplex"))
    failed = dataclasses.replace(
        ok, gamma=np.full_like(ok.gamma, np.nan), makespan=float("nan"),
        status="error")
    d = ok.diff(failed)
    assert d.get("gamma") == "nan-pattern"
    assert "status" in d and "makespan" in d
    # identical NaN patterns still diff clean (two failed plans)
    assert failed.diff(dataclasses.replace(failed)) == {}
