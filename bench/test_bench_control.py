"""The control of the check, at a size a test run holds: the reference
computed in bfloat16, put in the program's place, is not correct under
the cell's limits, nor is the program with half of its batch left out;
the program itself is. The faults of the sampled path move the numbers
read after the first plan refresh far from the program's own."""
import pytest

import compare
import control
from smallcells import small

SEEDS = (3, 2 ** 33 + 9)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_and_program_passes(seed):
    cell = small("gcn-reddit-rsc")
    r = control.readings(cell, seed, control=True, fault=True,
                         require_chip=False)
    ok, _ = compare.verdict(r["program"], cell.limits)
    assert ok, r
    ok, _ = compare.verdict(r["control"], cell.limits)
    assert not ok, r
    assert set(r) == {"seed", "program", "control",
                      *control.fault_names(cell)}
    ok, _ = compare.verdict(r["half_batch"], cell.limits)
    assert not ok, r


@pytest.mark.parametrize("seed", SEEDS)
def test_sage_control_fails_on_the_first_step(seed):
    cell = small("sage-reddit-rsc")
    r = control.readings(cell, seed, control=True, fault=False,
                         require_chip=False)
    ok, _ = compare.verdict(r["program"], cell.limits)
    assert ok, r
    ok, checks = compare.verdict(r["control"], cell.limits)
    assert not ok, r
    first = checks["first_loss_gap"]
    assert first["value"] > first["limit"], r


@pytest.mark.parametrize("workload", ["gcn-reddit-rsc", "sage-reddit-rsc"])
@pytest.mark.parametrize("fault", ["lowest_blocks", "rescaled_sample"])
def test_sampled_faults_move_the_refresh_gradient(workload, fault):
    cell = small(workload)
    r = control.readings(cell, SEEDS[0], control=False, fault=True,
                         require_chip=False)
    program = r["program"]["refresh_grad_gap"]
    assert r[fault]["refresh_grad_gap"] > max(100 * program, 0.02), r
