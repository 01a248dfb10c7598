"""The numbers that decide ``correct`` and the plan of the readings
behind their limits: as they were for an architecture without discrete
choices or faults of its own, and with ``choice`` and the module's
faults for one that has them."""

import types

import pytest
from tiny_cell import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import check, control, faults, run

PROG = {"loss": [0.25, 0.5, 0.75],
        "grad": {"a": 1.0, "b": 2.0, "c": 3e-7, "d": 4.0},
        "change": {"a": 0.5, "b": 0.25, "c": 0.125, "d": 1.0}}
REF = {"loss": [0.2500001, 0.4999, 0.75],
       "grad": {"a": 1.01, "b": 1.99, "c": 1e-7, "d": 4.0},
       "change": {"a": 0.5, "b": 0.2501, "c": 0.3, "d": 1.0001}}
LIMITS = {"loss": 6e-6, "grad": 2.5e-5, "change": 2e-5}


def test_numbers_without_choices_are_as_before():
    # the values check.numbers gave these records before ``choice``
    # existed, to the last digit; leaf "c" moves by round-off alone
    assert check.numbers(PROG, REF) == {
        "loss": (0.00020004000800157828, "step 1"),
        "grad": (0.006666666666666672, "a"),
        "change": (0.00024999999999997247, "b")}


def test_choice_is_the_largest_gap_with_its_step():
    nums = check.numbers(PROG, dict(REF, gap=[0.0, 3e-3, 1e-3]))
    assert nums["choice"] == (3e-3, "step 1")
    assert {k: nums[k] for k in LIMITS} == check.numbers(PROG, REF)


def test_verdict_holds_choice_to_its_limit():
    nums = {"loss": (1e-6, "step 0"), "grad": (1e-6, "a"),
            "change": (1e-6, "b"), "choice": (2e-3, "step 2")}
    ok, checks = check.verdict(nums, dict(LIMITS, choice=1e-3))
    assert ok is False and checks["choice"]["at"] == "step 2"
    assert check.verdict(nums, dict(LIMITS, choice=1e-2))[0] is True


@pytest.mark.parametrize("limits", [LIMITS, dict(LIMITS, choice=1e-3)],
                         ids=["choice_without_limit", "limit_without"])
def test_verdict_refuses_a_number_and_limit_apart(limits):
    nums = check.numbers(PROG, REF if "choice" in limits
                         else dict(REF, gap=[0.0]))
    with pytest.raises(ValueError):
        check.verdict(nums, limits)


def test_verdict_leaves_out_a_number_without_a_limit():
    # a training cell may leave out a number with no upper reading
    nums = check.numbers(PROG, REF)
    limits = {"loss": 1e-3, "grad": 1e-2}
    ok, checks = check.verdict(nums, limits)
    assert ok is True and set(checks) == set(limits)


def test_control_plan_without_faults_of_the_architecture():
    cell = run.load_cell("qwen2-1.5b.b4s1024")
    assert not hasattr(cell["arch"], "FAULTS")
    assert faults.of(cell["arch"]) == faults.FAULTS
    # the plan control.py made before architectures had faults
    assert control.plan_of(cell, 2**31 + 9, 2, 1, 2) == [
        ("sound", 2**31 + 9), ("sound", 2**31 + 10),
        ("half_batch", 2**31 + 11), ("half_batch", 2**31 + 12),
        ("altered_token", 2**31 + 13), ("altered_token", 2**31 + 14),
        ("control", 2**31 + 15)]


def _arch(**own):
    return types.SimpleNamespace(__file__="bench/archs/own.py", **own)


def test_control_plan_takes_the_architecture_faults():
    own = {"window_ignored": faults.altered_token}
    cell = {"arch": _arch(FAULTS=own)}
    assert faults.of(cell["arch"]) == {**faults.FAULTS, **own}
    assert [k for k, _ in control.plan_of(cell, 5, 1, 1, 1)] == [
        "sound", "half_batch", "altered_token", "window_ignored",
        "control"]


def test_a_fault_named_in_both_places_is_refused():
    with pytest.raises(ValueError, match="half_batch"):
        faults.of(_arch(FAULTS={"half_batch": faults.half_batch}))
