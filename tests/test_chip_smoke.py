"""chip_smoke.py's own logic against a CPU child at tiny sizes: tier-1's
black-box run of ``python -m tigerbeetle_tpu start``.

The script itself accepts no CPU; the expected platform, the tiny sizes and
the child's environment are passed from here.  The removal and rebuild of
libtb.so belongs to the script's ``main`` and is not exercised (it would
pull the library from under the other workers)."""

import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from tigerbeetle_tpu import jaxenv  # noqa: E402

TINY = chip_smoke.Sizes(
    batch=256, accounts=400, limit_accounts=64, transfers=16 * 256,
    sessions=4, special=48, lookups=120, accounts_log2=10,
    transfers_log2=14, ready_s=600.0, timeout_s=120.0,
)


def _run(tmp_path_factory, shards):
    env = jaxenv.child_env(cpu=True, n_devices=max(shards, 1))
    report = {}
    chip_smoke.run(
        TINY, seed=7, shards=shards, env=env, platform="cpu",
        workdir=str(tmp_path_factory.mktemp(f"smoke{shards}")),
        report=report,
    )
    return report


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    return _run(tmp_path_factory, 0)


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    return _run(tmp_path_factory, 4)


def test_one_chip_logic(one_chip):
    compared, server = one_chip["compared"], one_chip["server"]
    assert one_chip["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": 1,
        "executor": "device",
    }
    # The model comparison ran over every batch and every looked-up row.
    n_batches = sum(len(p.batches) for p in
                    chip_smoke.build_plan(TINY, 7).phases)
    assert compared["batches"] == n_batches
    assert compared["nonzero_codes"] > 0 and compared["rows"] > 0
    routes = server["routes"]
    assert routes["grouped"] > 0 and routes["general"] > 0
    assert routes["sequential"] > 0 and server["dispatches"] > 0


def test_four_chips_logic(four_chips):
    assert four_chips["device"]["count"] == 4
    assert four_chips["compared"]["rows"] > 0
    held = four_chips["server"]["ledger_bytes"]
    assert len(held) == 4 and min(held.values()) > 0


def test_wrong_expected_balance_fails(one_chip):
    want = copy.deepcopy(one_chip["want"])
    chip_smoke.compare(one_chip["got"], want)  # sanity: the copy is equal
    want["accounts"][0][0].credits_posted += 1
    with pytest.raises(chip_smoke.SmokeFailure, match="accounts lookup 0"):
        chip_smoke.compare(one_chip["got"], want)


def test_wrong_platform_fails(tmp_path):
    """A child that reports another platform than the expected one fails
    the run before any operation is sent (what main() relies on off-TPU)."""
    report = {}
    with pytest.raises(chip_smoke.SmokeFailure, match="need 'tpu'"):
        chip_smoke.run(
            TINY, seed=7, shards=0, env=jaxenv.child_env(cpu=True),
            platform="tpu", workdir=str(tmp_path), report=report,
        )
    assert report["device"]["platform"] == "cpu"
