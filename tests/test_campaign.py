"""The golden-eval campaign subsystem (repro.eval).

Pins the four properties the campaign is trusted for:

* **determinism** — the same spec produces byte-identical campaign.json,
  and every instance re-materializes exactly from (seed, cell_id, index);
* **classification** — hand-built cases land in each of the five classes,
  and a synthetic anomaly hard-fails via ``require_clean``;
* **end-to-end** — a tiny campaign runs clean through the serial auto and
  scipy backends and the batched engine, and the document validates;
* **gating** — ``scripts/check_campaign.py`` passes on a clean document
  vs its own distilled baseline and fails on anomalies / rate drops.

Plus the MULTIINST failure-signalling regression: the §2 motivating
instance past the divergence bound comes back as a structured infeasible
result, never an exception.
"""

import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from repro.api import Policy, Session
from repro.core.closed_form import LAMBDA_DIVERGENCE, example_instance
from repro.core.heuristics import (ALL_HEURISTICS, HeuristicResult,
                                   multi_inst, run_strategy)
from repro.core.instance import random_instance
from repro.eval import (CLASSES, CampaignAnomalyError, CampaignResult,
                        CampaignSpec, build_document, classify_instance,
                        load_campaign, render_markdown, run_campaign,
                        smoke_spec, validate_campaign, write_campaign)
from repro.eval.report import to_canonical_json

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def micro_spec(**kw) -> CampaignSpec:
    """A fast serial-backend campaign: 8 instances, no JAX compiles."""
    base = dict(
        name="micro", seed=11,
        topologies=("chain", "star"), return_ratios=(0.0,),
        releases=(False, True), m_values=(3,), n_loads_values=(2,),
        q_values=(1,), heterogeneity=(True,), comm_to_comp=(0.02, 2.0),
        instances_per_cell=1, backend="auto", matched_backend="auto",
    )
    base.update(kw)
    return CampaignSpec(**base)


def chain_case(seed=5, cc=2.0, q=1):
    """One chain instance + its LP artifact + resolved heuristic runs."""
    rng = np.random.default_rng(seed)
    inst = random_instance(rng, m=3, n_loads=2, q=q, with_latency=True,
                           comm_to_comp=cc)
    sess = Session(policy=Policy(backend="auto"))
    art = sess.solve(inst)
    runs = [run_strategy(n, f, inst) for n, f in ALL_HEURISTICS.items()]
    return inst, art, runs


# ------------------------------------------------------------ spec / grid


def test_spec_grid_shape_and_ids():
    spec = micro_spec()
    cells = spec.cells()
    assert len(cells) == 8  # 2 topo x 2 release x 2 comm_to_comp
    assert spec.n_instances == 8
    ids = [CampaignSpec.cell_id(c) for c in cells]
    assert len(set(ids)) == len(ids)
    assert ids[0] == "chain/ret0/rel0/m3/n2/q1/het1/cc0.02"


def test_spec_round_trip():
    spec = micro_spec()
    assert CampaignSpec.from_dict(spec.to_dict()) == spec
    assert CampaignSpec.from_dict(
        json.loads(json.dumps(spec.to_dict()))) == spec


def test_materialize_is_deterministic_and_seed_sensitive():
    spec = micro_spec()
    cell = spec.cells()[0]
    a = spec.materialize(cell, 0)
    b = spec.materialize(cell, 0)
    np.testing.assert_array_equal(a.platform.w, b.platform.w)
    np.testing.assert_array_equal(a.loads.v_comm, b.loads.v_comm)
    # a different index or seed draws a different instance
    c = spec.materialize(cell, 1)
    d = dataclasses.replace(spec, seed=spec.seed + 1).materialize(cell, 0)
    assert not np.array_equal(a.platform.w, c.platform.w)
    assert not np.array_equal(a.platform.w, d.platform.w)


def test_release_axis_draws_release_dates():
    spec = micro_spec()
    off = next(c for c in spec.cells() if not c["release"])
    on = dict(off, release=True)
    assert float(np.max(spec.materialize(off, 0).loads.release)) == 0.0
    assert float(np.max(spec.materialize(on, 0).loads.release)) > 0.0


def test_smoke_spec_meets_the_campaign_floor():
    spec = smoke_spec()
    assert spec.n_instances >= 200
    for axis in ("topologies", "return_ratios", "releases", "q_values"):
        assert len(getattr(spec, axis)) >= 2


# -------------------------------------------------------- determinism e2e


def test_campaign_json_bit_identical():
    spec = micro_spec()
    doc1 = build_document(run_campaign(spec))
    doc2 = build_document(run_campaign(spec))
    assert validate_campaign(doc1) == []
    assert to_canonical_json(doc1) == to_canonical_json(doc2)


# ------------------------------------------------------------- classifier


def test_classifier_lp_wins():
    inst, art, runs = chain_case(cc=2.0)
    c = classify_instance(inst, art, runs)
    assert c.label == "lp-wins"
    assert c.ratio is not None and c.ratio > 1.0
    assert c.best_strategy in ALL_HEURISTICS
    assert c.anomaly is None


def test_classifier_tie():
    # a "heuristic" replaying the LP's own schedule ties it exactly
    inst, art, _ = chain_case()
    sched = art.schedule()
    mirror = HeuristicResult(name="SIMPLE", instance=inst,
                             gamma=sched.gamma, schedule=sched)
    c = classify_instance(inst, art, [mirror])
    assert c.label == "tie"
    assert c.ratio == pytest.approx(1.0, abs=1e-12)


def test_classifier_heuristic_infeasible_on_star():
    rng = np.random.default_rng(7)
    inst = random_instance(rng, m=3, n_loads=2, q=1, topology="star",
                           return_ratio=0.5)
    art = Session(policy=Policy(backend="auto")).solve(inst)
    runs = [run_strategy(n, f, inst) for n, f in ALL_HEURISTICS.items()]
    c = classify_instance(inst, art, runs)
    assert c.label == "heuristic-infeasible"
    assert c.ratio is None and c.best_strategy is None
    assert all(e["failure"] == "unsupported" for e in c.strategies.values())


def test_classifier_lp_fallback():
    inst, art, runs = chain_case()
    art2 = dataclasses.replace(
        art, events=({"kind": "fallback", "backend": "auto",
                      "reason": "test"},))
    c = classify_instance(inst, art2, runs)
    assert c.label == "lp-fallback"
    assert c.lp_events == ["fallback"]


def test_classifier_synthetic_anomaly_and_require_clean():
    inst, art, runs = chain_case()
    # inflate the LP makespan: every feasible heuristic now "beats" it, and
    # with matched verification off the anomaly must stand
    worse = dataclasses.replace(art, makespan=art.makespan * 2.0)
    c = classify_instance(inst, worse, runs, matched_solve=None)
    assert c.label == "anomaly"
    assert c.anomaly["kind"] == "heuristic-beats-lp"
    result = CampaignResult(spec=micro_spec(), classifications=[c])
    assert result.domination_rate == 0.0
    with pytest.raises(CampaignAnomalyError, match="heuristic-beats-lp"):
        result.require_clean()


def test_classifier_matched_resolve_clears_false_anomaly():
    # same inflated artifact, but with the matched re-solve available the
    # candidate verifies against the LP at the heuristic's own structure
    inst, art, runs = chain_case()
    worse = dataclasses.replace(art, makespan=art.makespan * 2.0)
    sess = Session(policy=Policy(backend="auto"))
    c = classify_instance(inst, worse, runs, matched_solve=sess.solve)
    assert c.label != "anomaly"
    assert c.matched  # the lazy verification actually ran


def test_classifier_lp_failure_is_an_anomaly():
    inst, art, runs = chain_case()
    broken = dataclasses.replace(art, status="error")
    c = classify_instance(inst, broken, runs)
    assert c.label == "anomaly"
    assert c.anomaly["kind"] == "lp-failed"


# ------------------------------------------- multi_inst failure signalling


def test_multi_inst_divergent_instance_returns_structured_infeasible():
    # the §2/§3 motivating instance past the divergence bound: the [19]
    # construction cannot cover the load — that must be a clean result
    lam = 0.3
    assert lam < LAMBDA_DIVERGENCE
    r = multi_inst(example_instance(lam))
    assert r.failed and r.failure == "infeasible" and r.infeasible
    assert r.schedule is None
    # and the classifier counts it as a failed strategy, not a crash
    inst = example_instance(lam)
    art = Session(policy=Policy(backend="auto")).solve(inst)
    c = classify_instance(inst, art, [r])
    assert c.label == "heuristic-infeasible"
    assert c.strategies["MULTIINST"]["failure"] == "infeasible"


def test_multi_inst_unexpected_exception_is_an_error_result(monkeypatch):
    import repro.core.heuristics as h

    def boom(*a, **kw):
        raise RuntimeError("boom")

    monkeypatch.setattr(h, "_max_chunk", boom)
    r = h.multi_inst(example_instance(0.95))
    assert r.failed and r.failure == "error"
    assert "RuntimeError" in r.reason


def test_run_strategy_marks_out_of_model_instances_unsupported():
    rng = np.random.default_rng(3)
    star = random_instance(rng, m=3, n_loads=1, q=1, topology="star")
    r = run_strategy("MULTIINST", multi_inst, star)
    assert r.failed and r.failure == "unsupported"


# ------------------------------------------------------- e2e per backend


@pytest.mark.parametrize("backend", ["auto", "batched", "scipy"])
def test_tiny_campaign_end_to_end(backend):
    spec = micro_spec(name=f"tiny-{backend}", backend=backend,
                      releases=(False,), comm_to_comp=(0.02,))
    result = run_campaign(spec, strict=True)  # raises on any anomaly
    assert result.n == spec.n_instances == 2
    doc = build_document(result)
    assert validate_campaign(doc) == []
    assert doc["totals"]["counts"]["anomaly"] == 0
    assert doc["totals"]["domination_rate"] == 1.0


# -------------------------------------------------------- report / gating


def test_report_round_trip_and_markdown(tmp_path):
    result = run_campaign(micro_spec())
    doc = build_document(result)
    jp, mp = str(tmp_path / "campaign.json"), str(tmp_path / "campaign.md")
    write_campaign(doc, jp, mp)
    assert load_campaign(jp) == doc
    md = render_markdown(doc)
    assert "Domination rate: 100.00%" in md
    assert "MULTIINST" in md
    for label in CLASSES:
        assert label in md


def test_validate_campaign_catches_corruption():
    doc = build_document(run_campaign(micro_spec()))
    assert validate_campaign(doc) == []
    bad = json.loads(to_canonical_json(doc))
    bad["totals"]["counts"]["anomaly"] = 3
    assert validate_campaign(bad)  # counts no longer sum / rate inconsistent
    assert validate_campaign({"schema_version": 99})


def _load_checker():
    path = os.path.join(REPO, "scripts", "check_campaign.py")
    spec = importlib.util.spec_from_file_location("check_campaign", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_campaign_gate(tmp_path):
    checker = _load_checker()
    doc = build_document(run_campaign(micro_spec()))
    jp = str(tmp_path / "campaign.json")
    bp = str(tmp_path / "baseline.json")
    write_campaign(doc, jp)

    # distill a baseline from the document itself, then the gate holds
    assert checker.main(["--campaign", jp, "--baseline", bp,
                         "--write-baseline"]) == 0
    assert checker.main(["--campaign", jp, "--baseline", bp]) == 0

    # a raised baseline rate fails... unless domination drift is warn-only
    base = json.load(open(bp))
    base["domination_rate"] = 1.5
    json.dump(base, open(bp, "w"))
    assert checker.main(["--campaign", jp, "--baseline", bp]) == 1
    assert checker.main(["--campaign", jp, "--baseline", bp,
                         "--warn-only-domination"]) == 0

    # --smoke skips the identity comparison but still compares the rate
    base["domination_rate"] = 0.5
    base["name"], base["seed"], base["n"] = "other", 999, 1
    json.dump(base, open(bp, "w"))
    assert checker.main(["--campaign", jp, "--baseline", bp, "--smoke"]) == 0
    assert checker.main(["--campaign", jp, "--baseline", bp]) == 1

    # anomalies always fail, even with every escape hatch flipped
    base = checker.distill(doc)
    json.dump(base, open(bp, "w"))
    bad = json.loads(to_canonical_json(doc))
    row = bad["instances"][0]
    row["label"] = "anomaly"
    bad["totals"]["counts"]["anomaly"] = 1
    bad["totals"]["counts"][doc["instances"][0]["label"]] -= 1
    bad["totals"]["domination_rate"] = 1.0 - 1.0 / bad["totals"]["n"]
    bad["anomalies"] = [{"cell_id": row["cell_id"], "index": row["index"],
                         "content_key": row["content_key"],
                         "anomaly": {"kind": "heuristic-beats-lp"}}]
    jbad = str(tmp_path / "bad.json")
    write_campaign(bad, jbad)
    assert checker.main(["--campaign", jbad, "--baseline", bp, "--smoke",
                         "--warn-only-domination"]) == 1


def test_cli_main_smoke_tier(tmp_path, monkeypatch, capsys):
    import repro.eval.__main__ as cli

    # stand in a micro spec for the smoke tier so the CLI path stays fast
    monkeypatch.setattr(cli, "smoke_spec", lambda: micro_spec())
    out = str(tmp_path / "out")
    assert cli.main(["--smoke", "--out", out, "--strict"]) == 0
    assert load_campaign(os.path.join(out, "campaign.json"))
    assert os.path.exists(os.path.join(out, "campaign.md"))
    assert "wrote" in capsys.readouterr().out


def test_cli_strict_fails_on_anomaly(tmp_path, monkeypatch):
    import repro.eval.__main__ as cli

    monkeypatch.setattr(cli, "smoke_spec", lambda: micro_spec())

    real_run = cli.run_campaign

    def sabotaged(spec, **kw):
        result = real_run(spec, **kw)
        result.classifications[0] = dataclasses.replace(
            result.classifications[0], label="anomaly",
            anomaly={"kind": "heuristic-beats-lp"})
        return result

    monkeypatch.setattr(cli, "run_campaign", sabotaged)
    assert cli.main(["--smoke", "--out", str(tmp_path), "--strict"]) == 1


def test_campaign_found_simplex_mis_convergence_regression():
    # Found by the first full sweep: on this star/returns LP the dense
    # simplex exited "optimal" with a port-serialization row violated by
    # ~0.24 and an objective *below* the true optimum; the serial path now
    # verifies primal feasibility and rescues through HiGHS.  The instance
    # re-materializes exactly from its report coordinates — the replay
    # workflow the campaign documents.
    from repro.core.solver import solve
    from repro.eval import full_spec

    spec = full_spec()
    cell_id = "star/ret0.75/rel0/m2/n3/q4/het1/cc0.02"
    cell = next(c for c in spec.cells() if CampaignSpec.cell_id(c) == cell_id)
    inst = spec.materialize(cell, 0)
    golden = 976.1527780792386  # HiGHS optimum; replay matches it exactly
    for backend in ("simplex", "auto"):
        rep = solve(inst, backend=backend, validate=True)  # used to raise
        assert rep.status == "optimal"
        assert abs(rep.makespan - golden) <= 1e-6 * golden
