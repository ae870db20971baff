"""`benchmarks/generators/tiered_plain.py`: the plan `tiered-plain-s8` sends.

What lets the benchmark's unedited check replay session by session (a session
re-sends only what it had acknowledged itself, so any interleaving of the
sessions' requests gives the same codes and rows), and the shapes the issue
names: `plain-s8`'s plan but for the retries, every 8th window request of a
session from its 4th, 128 events byte for byte from its first 48 preloaded
requests."""

import json
import os

import numpy as np
import pytest

from benchmarks.generators import ledger_mix, tiered_plain
from benchmarks.harness import check
from benchmarks.reference.ledger import ReferenceLedger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks/traffic/plain-tiered-s8.json")) as f:
    REAL = json.load(f)
SMALL = dict(REAL, accounts=300, batch=64, preload_per_session=50,
             window_cap_per_session=20, retry_events=16)
SEEDS = [3, 4800000021]          # one over 2**31, as the driver's seeds are


def _preload(plan):
    (queues,) = [p["queues"] for p in plan["setup"] if p["name"] == "preload"]
    return queues


def test_the_real_mix_is_plain_s8_but_for_its_lengths_and_retries():
    with open(os.path.join(ROOT, "benchmarks/traffic/plain-s8.json")) as f:
        plain = json.load(f)
    for key in ("accounts", "batch", "sessions", "amount_max",
                "lookup_sample"):
        assert REAL[key] == plain[key], key
    assert REAL["generator"] == "tiered_plain"
    assert (REAL["preload_per_session"], REAL["window_cap_per_session"]) == (
        129, 61)
    assert (REAL["retry_every"], REAL["retry_first"], REAL["retry_events"],
            REAL["retry_sources"]) == (8, 3, 128, 48)
    assert tiered_plain.retry_positions(REAL) == [3, 11, 19, 27, 35, 43, 51,
                                                  59]
    assert REAL["allowed_codes"] == [0, 46]
    assert set(REAL) - set(plain) == {"retry_every", "retry_first",
                                      "retry_events", "retry_sources"}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_plan_is_ledger_mixs_with_the_retries_written_over(seed):
    plan = tiered_plain.build(SMALL, seed)
    plain = ledger_mix.build(dict(SMALL, cycle=["plain"]), seed)
    assert [p["name"] for p in plan["setup"]] == ["accounts", "preload"]
    assert plan["account_ids"] == plain["account_ids"]
    assert plan["unused_ids"] == plain["unused_ids"]
    for mine, theirs in zip(plan["setup"], plain["setup"]):
        for q_mine, q_theirs in zip(mine["queues"], theirs["queues"]):
            assert [r.tobytes() for _op, r in q_mine] == [
                r.tobytes() for _op, r in q_theirs]
    positions = tiered_plain.retry_positions(SMALL)
    assert positions == [3, 11, 19]
    for s, queue in enumerate(plan["window"]):
        assert len(queue) == SMALL["window_cap_per_session"]
        for at, (operation, rows) in enumerate(queue):
            before = plain["window"][s][at][1]
            assert operation == "create_transfers" and len(rows) == 64
            if at not in positions:
                assert rows.tobytes() == before.tobytes()
                continue
            source = _preload(plan)[s][positions.index(at)][1]
            assert rows[:16].tobytes() == source[:16].tobytes()
            assert rows[16:].tobytes() == before[16:].tobytes()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_seed_gives_the_same_plan_and_another_seed_another(seed):
    a, b = tiered_plain.build(SMALL, seed), tiered_plain.build(SMALL, seed)
    other = tiered_plain.build(SMALL, seed + 1)
    flat = lambda plan: [r.tobytes() for q in plan["window"] for _op, r in q]
    assert flat(a) == flat(b) != flat(other)


def test_retry_sources_wrap_around_the_sessions_oldest_requests():
    mix = dict(SMALL, retry_sources=2)
    plan = tiered_plain.build(mix, 9)
    for s, queue in enumerate(plan["window"]):
        for k, at in enumerate(tiered_plain.retry_positions(mix)):
            source = _preload(plan)[s][k % 2][1]
            assert queue[at][1][:16].tobytes() == source[:16].tobytes()


@pytest.mark.parametrize("seed", SEEDS)
def test_ids_are_unique_but_for_the_retried_and_a_session_resends_its_own(
        seed):
    plan = tiered_plain.build(SMALL, seed)
    sessions = SMALL["sessions"]
    positions = tiered_plain.retry_positions(SMALL)
    retried = sessions * len(positions) * SMALL["retry_events"]
    own = []
    for s in range(sessions):
        sent = np.concatenate([r["id_lo"] for _op, r in _preload(plan)[s]])
        again = np.concatenate([plan["window"][s][at][1]["id_lo"][:16]
                                for at in positions])
        assert set(again.tolist()) <= set(sent.tolist())   # its own, only
        own.append(np.concatenate(
            [sent] + [r["id_lo"] for _op, r in plan["window"][s]]))
    ids = np.concatenate(own)
    assert len(ids) - len(np.unique(ids)) == retried
    assert ids.max() < plan["unused_ids"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_answers_exists_and_any_interleaving_agrees(seed):
    """Session by session (the benchmark's replay) and round-robin (nearer
    what the server commits): the same codes, the same rows."""
    plan = tiered_plain.build(SMALL, seed)
    counts = [len(q) for q in plan["window"]]
    by_session, round_robin = ReferenceLedger(), ReferenceLedger()
    check.replay_setup(by_session, plan)
    check.replay_setup(round_robin, plan)
    want = check.replay_window(by_session, plan, counts)
    got = [[None] * n for n in counts]
    for k in range(max(counts)):
        for s, queue in enumerate(plan["window"]):
            got[s][k] = round_robin.execute(*queue[k])
    assert got == want
    positions = tiered_plain.retry_positions(SMALL)
    for queue in want:
        for at, codes in enumerate(queue):
            assert codes == ([(i, tiered_plain.EXISTS) for i in range(16)]
                             if at in positions else [])
    ids = check.sample_transfer_ids(plan, counts, seed,
                                    dict(SMALL, lookup_sample=400))
    assert check._rows_differing(by_session.lookup_transfers(ids),
                                 round_robin.lookup_transfers(ids)) == 0
    accounts = plan["account_ids"]
    assert check._rows_differing(by_session.lookup_accounts(accounts),
                                 round_robin.lookup_accounts(accounts)) == 0


def test_a_mix_that_retries_what_was_never_preloaded_is_refused():
    with pytest.raises(ValueError):
        tiered_plain.build(dict(SMALL, retry_sources=51), 1)
    with pytest.raises(ValueError):
        tiered_plain.build(dict(SMALL, retry_events=65), 1)
