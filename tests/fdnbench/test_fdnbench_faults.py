"""``correct`` comes out false when the timed path is broken underneath
(each fault of fdnbench/faults.py a cell can have), and the control put
in the decision's place fails the decision numbers' limits."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from fdnbench import check, faults, harness  # noqa: E402

SHORT = {"warmup_sim_s": 5.0}


def harness_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return [w["name"] for w in json.load(fh)["workloads"]]


@pytest.mark.parametrize("cell", harness_cells())
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_makes_run_incorrect(fault, cell):
    with faults.FAULTS[fault]():
        out = harness.run_cell(cell, 2 ** 31 + 3, 0.5, False, cpu=True,
                               mix_override=SHORT)
    assert out.result["correct"] is False
    failed = [k for k, v in out.result["checks"].items()
              if v["value"] > v["limit"]]
    want = {"stuck": "unrouted_rows", "half-batch": "unrouted_rows",
            "misroute": "misrouted_rows",
            "alter-answer": "infeasible_choices",
            "drop-observation": "estimator_count_mismatches"}[fault]
    assert want in failed


@pytest.mark.parametrize("cell", harness_cells())
def test_a_control_fails_the_cell_limits(cell):
    # the controls put in the decision's place at the cell's load: at
    # least one of them must fail a number the cell compares
    out = harness.run_cell(cell, 2 ** 31 + 5, 3.0, False, cpu=True,
                           mix_override=SHORT)
    assert out.result["correct"] is True
    cap, ref, lim = out.capture, out.reference, out.cell.limits
    controls = [check.held_control(cap, ref, frozen=True),
                check.held_control(cap, ref, frozen=False),
                check.replaced_control(ref, check.reference(
                    cap, out.fleet, degrade=False))]
    assert any(c[k] > lim[k]["limit"] for c in controls for k in c
               if k in lim)


@pytest.mark.parametrize("cell", harness_cells())
@pytest.mark.parametrize("frozen", [True, False])
def test_held_estimates_fail_both_estimator_limits(cell, frozen):
    # estimator columns one batch old (or the window's first) in each
    # decision's place: the recomputation from the completions sees it
    out = harness.run_cell(cell, 2 ** 31 + 9, 2.0, False, cpu=True,
                           mix_override=SHORT)
    assert out.result["correct"] is True
    got = check.held_estimates(out.capture, out.fleet, out.estimates,
                               frozen)
    lim = out.cell.limits
    for k in ("estimator_count_mismatches", "estimator_gap"):
        assert got[k] > lim[k]["limit"], (k, got[k])
