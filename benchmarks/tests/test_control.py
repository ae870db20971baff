"""The control of `correct` (see `control.py`) at a size a test run can
hold: the sound run is correct, every control is not, on three seeds."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control  # noqa: E402
from benchmarks.generators import ledger_mix  # noqa: E402
from benchmarks.harness import check  # noqa: E402

MIX = {
    "generator": "ledger_mix", "accounts": 200, "batch": 128, "sessions": 4,
    "cycle": ["pending", "resolve"],
    "resolve": {"post_pct": 80, "void_pct": 15},
    "preload_per_session": 4, "window_cap_per_session": 4,
    "lookup_sample": 300,
}


@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_sound_run_is_correct_and_every_control_is_not(seed, tmp_path):
    counts = [4, 3, 4, 2]             # the last session ends on a resolve
    runs = control.verdicts(MIX, seed, counts, str(tmp_path))
    assert check.verdict(runs["sound"]), runs["sound"]
    assert runs["sound"]["transfer_rows_compared"][0] == 225
    for fault in control.CONTROLS:
        numbers = runs[fault]
        assert not check.verdict(numbers), (fault, numbers)
        failing = {k for k, (v, limit) in numbers.items()
                   if limit is not None and v > limit}
        assert failing <= {"account_rows_differing",
                           "transfer_rows_differing"}, (fault, failing)


def test_a_repeated_or_zero_timestamp_is_not_correct(tmp_path):
    plan = ledger_mix.build(MIX, 5)
    counts = [2, 2, 2, 2]
    want = control.expected(plan, counts, 5, MIX,
                            str(tmp_path / "expected.npz"))
    setup_sent, window_sent, accounts, transfers = control.serve(
        plan, counts, 5, MIX)
    accounts["timestamp"][3] = accounts["timestamp"][4]
    transfers["timestamp"][0] = 0
    numbers = check.compare(want, setup_sent, window_sent, accounts,
                            transfers)
    assert numbers["rows_with_bad_timestamp"] == (2, 0)
    assert not check.verdict(numbers)
