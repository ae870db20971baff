"""The CPU rehearsal of `run.py`'s logic for both mixes at a tiny size:
`run_cell` against a CPU child, through `cpu_cell.py` (run.py itself accepts
no CPU).  The cells, the configuration, the mixes and one per-layer metric
exist only as files and entries added to a temporary copy (conftest.py):
that they are found and run is the proof that a later PR needs no edit."""

import os

import pytest


@pytest.fixture(scope="module")
def plain(cpu_cell):
    return cpu_cell("tiny-plain", 7, 3, 0)


@pytest.fixture(scope="module")
def twophase_traced(cpu_cell):
    return cpu_cell("tiny-twophase", 3000000011, 4, 1)


def test_plain_cell_is_correct(plain):
    rc, out, err = plain
    assert rc == 0, err[-3000:]
    assert out["correct"] is True and out["failed"] == 0
    numbers = out["numbers"]
    assert numbers["requests_compared"][0] == 4 + 16 + out["attempted"]
    assert numbers["account_rows_compared"][0] == 300
    assert numbers["transfer_rows_compared"][0] == 300
    assert set(out["end_to_end"]) == {"accepted_tx_s", "setup_s"}
    assert all(v > 0 for v in out["end_to_end"].values())
    assert out["device"]["executor"] == "device"


def test_twophase_cell_traced_reads_every_layer(twophase_traced):
    rc, out, err = twophase_traced
    assert rc == 0, err[-3000:]
    assert out["correct"] is True
    layer = out["per_layer"]
    # Files added to the copy were found by name: the new metric is read.
    assert layer["answered"] == out["attempted"]
    for name in ("batch_p95_ms", "batch_p50_ms", "group_batches",
                 "wal_fsync_ms", "dispatches_per_batch",
                 "readback_wait_ms", "compiles_in_window", "device_idle_pct"):
        assert name in layer, name
    routes = out["observations"]["window_routes"]
    # A session's window goes on from its preload: pending, resolve, ...
    resolving = sum(n // 2 for n in
                    out["observations"]["window_requests_per_session"])
    assert routes["general"] == resolving and routes["sequential"] == 0
    assert routes["fast"] + routes["grouped"] == out["attempted"] - resolving


def test_broken_timed_path_is_not_correct(cpu_cell, tiny_copy):
    """The rest of a run with an answer altered where it is produced (the
    server's first looked-up account row, one unit too many)."""
    rc, out, err = cpu_cell(
        "tiny-plain", 11, 2, 0, "--server-main",
        os.path.join(tiny_copy, "benchmarks/tests/broken_server_main.py"))
    assert rc == 0, err[-3000:]
    assert out["correct"] is False
    assert out["numbers"]["account_rows_differing"] == [1, 0]
    assert out["numbers"]["requests_with_wrong_codes"] == [0, 0]


def test_another_platform_than_expected_fails_before_measuring(cpu_cell):
    """What `main` relies on off a TPU: it passes "tpu", the child says
    "cpu", and the run ends with no result."""
    rc, out, err = cpu_cell("tiny-plain", 7, 2, 0,
                            "--expect-platform", "tpu")
    assert rc != 0 and out is None
    assert "need 'tpu'" in err
