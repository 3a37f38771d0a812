"""Monte Carlo campaigns: determinism, samplers, statistics, CLI contract."""
import json

import pytest

from repro.scenarios import montecarlo
from repro.scenarios.engine import run_scenario
from repro.scenarios.montecarlo import (CampaignSpec, get, names,
                                        run_campaign, sample_trial)
from repro.scenarios.report import render_markdown
from repro.scenarios.run import main as cli_main
from repro.scenarios.spec import InjectFault, ScenarioSpec, JobSpec
from repro.scenarios.stats import (aggregate, baseline_fault_downtime_s,
                                   mean_ci, percentiles, trial_metrics)

TINY = dict(n_trials=3, gpus=32, duration_s=3600.0)


def tiny_campaign(seed=0, **over):
    return CampaignSpec(name="tiny", seed=seed,
                        **{**TINY, "faults_per_hour": 2.0, **over})


# ---------------------------------------------------------------------------
# seeded determinism
# ---------------------------------------------------------------------------

def test_campaign_bit_identical_for_same_seed():
    a = run_campaign(tiny_campaign()).to_json()
    b = run_campaign(tiny_campaign()).to_json()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_campaign_identical_across_worker_counts():
    a = run_campaign(tiny_campaign(), workers=1).to_json()
    b = run_campaign(tiny_campaign(), workers=2).to_json()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


@pytest.mark.parametrize("backend", ["jax", "auto"])
def test_worker_pool_refused_off_numpy(backend):
    """Each pool worker would try to take the accelerator."""
    with pytest.raises(ValueError, match="one process at a time"):
        run_campaign(tiny_campaign(backend=backend), workers=2)


def test_campaign_seed_changes_output_and_is_surfaced():
    a = run_campaign(tiny_campaign(seed=0)).to_json()
    b = run_campaign(tiny_campaign(seed=9)).to_json()
    assert a["seed"] == 0 and b["seed"] == 9
    assert json.dumps(a, sort_keys=True) != json.dumps(b, sort_keys=True)
    # every trial record carries its own (seed-derived) engine seed
    assert all("seed" in t for t in a["trials"])
    assert [t["seed"] for t in a["trials"]] != [t["seed"] for t in b["trials"]]


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------

def test_sample_trial_is_deterministic_and_valid():
    cam = tiny_campaign(link_flaps_per_hour=1.0)
    for i in range(4):
        s1 = sample_trial(cam, i)
        s2 = sample_trial(cam, i)
        assert s1 == s2
        assert s1.telemetry_ranks == cam.gpus
        assert s1.n_nodes == cam.gpus // cam.ranks_per_node
        for ev in s1.events:
            assert 0.0 <= ev.t <= s1.duration_s
            if isinstance(ev, InjectFault):
                assert ev.error_class is not None
                assert 0 <= ev.rank < cam.gpus
    # trials draw distinct populations
    assert sample_trial(cam, 0) != sample_trial(cam, 1)


def test_sampled_faults_follow_table1_classes():
    cam = tiny_campaign(n_trials=8, faults_per_hour=4.0)
    classes = {ev.error_class
               for i in range(cam.n_trials)
               for ev in sample_trial(cam, i).events
               if isinstance(ev, InjectFault)}
    from repro.core.faults import TABLE1
    assert classes <= {c.name for c in TABLE1}
    assert len(classes) >= 3          # the mix is actually sampled


def test_registry_overrides():
    assert "fleet_smoke" in names() and "fleet_1024" in names()
    cam = get("fleet_smoke", seed=5, n_trials=2, gpus=16)
    assert (cam.seed, cam.n_trials, cam.gpus) == (5, 2, 16)
    with pytest.raises(KeyError):
        get("nope")


# ---------------------------------------------------------------------------
# statistics against known ground truth
# ---------------------------------------------------------------------------

def _fault(acted, localized, kind="crash", det=30.0):
    return {"kind": kind, "acted": acted, "localized": localized,
            "detection_s": det,
            "phases": {"detection_s": det, "diagnosis_isolation_s": 400.0,
                       "post_checkpoint_s": 100.0,
                       "re_initialization_s": 330.0}}


def _report(faults, goodput=0.8):
    return {"scenario": "x", "seed": 1, "fabric": "c4p", "duration_s": 3600.0,
            "restarts": len(faults),
            "detection": {"n_faults": len(faults), "faults": faults},
            "downtime": {"fraction_of_duration": 0.1},
            "goodput": {"fraction": goodput},
            "network": {"n_events": 0, "detections": []},
            "ab": {"gain_pct": 50.0, "c4p_effective_gbps": 3.0,
                   "ecmp_effective_gbps": 2.0}}


def test_precision_recall_against_known_ground_truth():
    """1 TP + 1 FP (acted, wrong node) + 1 FN (missed) => P=0.5, R=1/3."""
    rep = _report([_fault(True, True), _fault(True, False),
                   _fault(False, False)])
    t = trial_metrics(rep)
    assert (t["true_positives"], t["false_positives"],
            t["false_negatives"]) == (1, 1, 1)
    agg = aggregate([t])
    assert agg["detection"]["precision"] == pytest.approx(0.5)
    assert agg["detection"]["recall"] == pytest.approx(1 / 3)
    # only acted faults contribute detection latencies
    assert agg["detection"]["latency_s"]["n"] == 2


def test_mttr_and_baseline_counterfactual():
    rep = _report([_fault(True, True)])
    t = trial_metrics(rep)
    assert t["mttr_s"] == [pytest.approx(860.0)]
    # baseline: hang timeout (crash blocks peers) + manual median +
    # half the infrequent checkpoint period + same reinit
    from repro.core.downtime import BASELINE_JUN23 as P
    expect = (P.hang_timeout_s + P.manual_diag_median_s
              + 0.5 * P.checkpoint_period_s + 330.0)
    assert t["baseline_mttr_s"] == [pytest.approx(expect)]
    assert baseline_fault_downtime_s(_fault(True, True, kind="slow_src")) == \
        pytest.approx(P.crash_notice_s + P.manual_diag_median_s
                      + 0.5 * P.checkpoint_period_s + 330.0)


def test_aggregate_claim_brackets_shape():
    agg = aggregate([trial_metrics(_report([_fault(True, True)]))])
    for key, block in (("overhead", "cut_pct_points"),
                       ("communication", "cost_cut_pct"),
                       ("efficiency", "gain_pct")):
        c = agg[key][block]
        assert {"mean", "ci_lo", "ci_hi", "paper_lo", "paper_hi",
                "brackets_paper"} <= set(c)


def test_mean_ci_and_percentiles_basics():
    assert mean_ci([])["mean"] is None
    one = mean_ci([2.0])
    assert one["mean"] == 2.0 and one["ci_lo"] == one["ci_hi"] == 2.0
    sym = mean_ci([1.0, 3.0])
    assert sym["mean"] == 2.0 and sym["ci_lo"] == pytest.approx(4 - sym["ci_hi"])
    ps = percentiles([1.0, 2.0, 3.0, 4.0])
    assert ps["p50"] == pytest.approx(2.5) and ps["n"] == 4


def test_end_to_end_trial_localizes_known_fault():
    """A campaign-shaped spec with one scripted crash yields exactly one TP."""
    spec = ScenarioSpec(
        name="known", description="", seed=3, duration_s=3600.0,
        telemetry_ranks=32, n_nodes=4,
        jobs=(JobSpec(0, tuple(range(16))),),
        events=(InjectFault(t=900.0, job_id=0, kind="crash", rank=9),))
    t = trial_metrics(run_scenario(spec))
    assert (t["n_faults"], t["true_positives"], t["false_negatives"]) == (1, 1, 0)


# ---------------------------------------------------------------------------
# report content
# ---------------------------------------------------------------------------

def test_report_brackets_efficiency_with_ci():
    rep = run_campaign(tiny_campaign(n_trials=4)).to_json()
    eff = rep["aggregates"]["efficiency"]["gain_pct"]
    assert eff["ci_lo"] <= eff["mean"] <= eff["ci_hi"]
    det = rep["aggregates"]["detection"]
    assert 0.0 <= det["precision"] <= 1.0 and 0.0 <= det["recall"] <= 1.0
    assert rep["aggregates"]["overhead"]["mttr_s"]["p50"] is not None
    md = render_markdown(rep)
    assert "Paper-claim brackets" in md and "precision" in md


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

def test_cli_list_includes_campaigns(capsys):
    assert cli_main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in montecarlo.names():
        assert name in out
    assert "campaign:" in out


def test_cli_campaign_json_contract(tmp_path, capsys):
    rc = cli_main(["--campaign", "fleet_smoke", "--trials", "2",
                   "--gpus", "32", "--seed", "5",
                   "--json", str(tmp_path) + "/", "--md", str(tmp_path) + "/"])
    assert rc == 0
    rep = json.loads((tmp_path / "fleet_smoke.json").read_text())
    assert rep["name"] == "fleet_smoke"
    assert rep["seed"] == 5                      # --seed reaches the sampler
    assert rep["campaign"]["gpus"] == 32
    assert rep["n_trials"] == 2 and len(rep["trials"]) == 2
    assert {"detection", "overhead", "communication", "efficiency"} <= \
        set(rep["aggregates"])
    assert (tmp_path / "fleet_smoke.md").read_text().startswith("# Campaign")
    out = capsys.readouterr().out
    assert "campaign      : fleet_smoke" in out


def test_cli_campaign_json_stdout(capsys):
    rc = cli_main(["--campaign", "fleet_smoke", "--trials", "1",
                   "--gpus", "32", "--json", "-"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["name"] == "fleet_smoke" and rep["n_trials"] == 1


def test_cli_scenario_seed_threaded(tmp_path):
    rc = cli_main(["--scenario", "single_nic_down", "--seed", "4",
                   "--json", str(tmp_path) + "/"])
    assert rc == 0
    rep = json.loads((tmp_path / "single_nic_down.json").read_text())
    assert rep["seed"] == 4


# ---------------------------------------------------------------------------
# mixed fault families (PR 8): per-family P/R + attribution in the report
# ---------------------------------------------------------------------------

def mixed_campaign(seed=0, **over):
    return CampaignSpec(name="tiny_mixed", seed=seed,
                        **{**TINY, "faults_per_hour": 1.0,
                           "divergence_faults_per_hour": 2.0,
                           "attribution": True,
                           "compare_fabrics": False, **over})


def test_mixed_campaign_samples_both_families():
    from repro.core.faults import DIVERGENCE_TABLE

    div_classes = {c.name for c in DIVERGENCE_TABLE}
    spec = mixed_campaign(n_trials=6)
    fams = set()
    for i in range(spec.n_trials):
        trial = sample_trial(spec, i)
        assert trial.attribution and trial.divergence
        for ev in trial.events:
            if isinstance(ev, InjectFault):
                fams.add("divergence" if ev.error_class in div_classes
                         else "comm")
    assert fams == {"comm", "divergence"}


def test_mixed_campaign_per_family_keys_and_determinism():
    a = run_campaign(mixed_campaign(), workers=1).to_json()
    b = run_campaign(mixed_campaign(), workers=2).to_json()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)

    det = a["aggregates"]["detection"]
    fams = det["per_family"]
    assert set(fams) >= {"divergence"}
    for fam, row in fams.items():
        assert {"n_faults", "true_positives", "false_positives",
                "false_negatives", "precision", "recall"} <= set(row), fam
        assert row["n_faults"] == (row["true_positives"] +
                                   row["false_positives"] +
                                   row["false_negatives"])
    att = det["attribution"]
    assert {"attempts", "hits", "hit_rate"} <= set(att)
    if att["attempts"]:
        assert 0.0 <= att["hit_rate"] <= 1.0


def test_fleet_mixed_registered_and_overridable():
    cam = get("fleet_mixed", n_trials=2, gpus=32)
    assert cam.divergence_faults_per_hour > 0 and cam.attribution
    assert cam.n_trials == 2 and cam.gpus == 32
    cam_off = get("fleet_mixed", attribution=False)
    assert cam_off.attribution is False
