import math

import numpy as np
import pytest

import compare

R = 2   # the first step after a plan refresh, in these small runs


def run(losses, grads, p0, p_last, grads_r=None):
    return {"losses": losses,
            "grads": {0: grads, R: grads if grads_r is None else grads_r},
            "params": {0: p0, compare.UPDATE_STEPS: p_last}}


REF = run([2.0, 1.5, 1.2, 1.0, 0.9],
          {"w": np.array([3.0, 4.0]), "b": np.array([1e-9]),
           "g": np.array([1.0])},
          {"w": np.zeros(2), "b": np.zeros(1), "g": np.ones(1)},
          {"w": np.array([0.3, 0.4]), "b": np.array([0.03]),
           "g": np.array([1.1])})


def test_identical_runs_read_zero():
    assert compare.gaps(REF, REF, R) == {k: 0.0 for k in compare.NUMBERS}


def test_worst_step_and_worst_leaf():
    side = run([2.0, 1.5, 1.203, 1.0, 0.9], {"w": np.array([3.0, 4.05]),
                                             "b": np.array([5e-9]),
                                             "g": np.array([1.0])},
               REF["params"][0], {"w": np.array([0.3, 0.4]),
                                  "b": np.array([0.09]),  # still leaf: ignored
                                  "g": np.array([1.2])})
    g = compare.gaps(side, REF, R)
    assert g["loss_gap"] == pytest.approx(0.003 / 1.2)
    # |g| norms 5.0 vs ~5.0403; the median leaf norm (1.0) floors the
    # nought bias, whose gap is ~4e-9
    assert g["grad_gap"] == pytest.approx((math.hypot(3, 4.05) - 5) / 5)
    # leaf g moved 0.2 against 0.1, over the median moved leaf's 0.3;
    # b is left out (its reference gradient is nought)
    assert g["update_gap"] == pytest.approx(0.1 / 0.3)


def test_first_loss_gap_reads_step_zero_alone():
    side = run([2.002, 1.5, 1.26, 1.0, 0.9], REF["grads"][0],
               REF["params"][0], REF["params"][compare.UPDATE_STEPS])
    g = compare.gaps(side, REF, R)
    assert g["first_loss_gap"] == pytest.approx(0.002 / 2.0)
    assert g["loss_gap"] == pytest.approx(0.06 / 1.2)


def test_refresh_numbers_read_the_steps_after_the_refresh():
    side = run([2.0, 1.5, 1.2, 1.01, 0.9], REF["grads"][0],
               REF["params"][0], REF["params"][compare.UPDATE_STEPS],
               grads_r={"w": np.array([3.0, 4.0]), "b": np.array([1e-9]),
                        "g": np.array([1.5])})
    g = compare.gaps(side, REF, R)
    assert g["loss_gap"] == g["grad_gap"] == g["update_gap"] == 0.0
    # step 2 is both a first step and a refresh step; step 3 is the worst
    assert g["refresh_loss_gap"] == pytest.approx(0.01 / 1.0)
    assert g["refresh_grad_gap"] == pytest.approx(0.5 / 1.0)


def test_state_unchanged_reads_one_and_nan_reads_inf():
    zero = {k: np.zeros_like(v) for k, v in REF["grads"][0].items()}
    still = run(REF["losses"], zero, REF["params"][0], REF["params"][0],
                grads_r=zero)
    g = compare.gaps(still, REF, R)
    assert g["grad_gap"] == pytest.approx(1.0)
    assert g["refresh_grad_gap"] == pytest.approx(1.0)
    assert g["update_gap"] == pytest.approx(1.0)
    broken = dict(REF, losses=[2.0, float("nan"), 1.2, 1.0, 0.9])
    assert compare.gaps(broken, REF, R)["loss_gap"] == math.inf


def test_verdict_compares_only_numbers_with_a_limit():
    ok, checks = compare.verdict(
        {"loss_gap": 1e-4, "refresh_loss_gap": 0.3, "grad_gap": 0.5,
         "refresh_grad_gap": 1e-3, "update_gap": 0.01},
        {"loss_gap": 1e-3, "refresh_grad_gap": 0.01, "update_gap": 0.1})
    assert ok and list(checks) == ["loss_gap", "refresh_grad_gap",
                                   "update_gap"]
    ok, _ = compare.verdict({"loss_gap": math.inf, "refresh_loss_gap": 0.0,
                             "grad_gap": 0.0, "refresh_grad_gap": 0.0,
                             "update_gap": 0.0}, {"loss_gap": 1e-3})
    assert not ok
