"""How `correct` is decided: what the timed path answered against the plain
reference (`benchmarks/reference/ledger.py`), exactly.

Compared, each with the limit 0 (an exact comparison):

- requests whose result codes differ from the reference's: every request of
  set-up and window;
- account rows that differ: ALL accounts, looked up after the window closed;
- transfer rows that differ: a sample of ids drawn from the seed — rows of
  set-up and window (plain, pending, posted, voided) and ids never created;
- rows with a zero or repeated `timestamp` (the server's clock: the one field
  the reference cannot know);
- requests that failed, and a server that did not stop cleanly.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmarks.harness import procs


def load_generator(mix: dict):
    return importlib.import_module(
        f"benchmarks.generators.{mix['generator']}")


def sample_transfer_ids(plan: dict, counts: Sequence[int], seed: int,
                        mix: dict) -> List[int]:
    """`lookup_sample` ids from the seed: three quarters among the transfers
    sent (set-up and the window's answered requests), one quarter never
    created.  The same in the parent and in the reference's process."""
    sent = [rows["id_lo"] for phase in plan["setup"]
            for queue in phase["queues"] for op, rows in queue
            if op == "create_transfers"]
    sent += [rows["id_lo"] for queue, n in zip(plan["window"], counts)
             for _op, rows in queue[:n]]
    created = np.concatenate(sent)
    rng = np.random.default_rng([seed, 0x5A])
    n = mix["lookup_sample"]
    n_created = min(n - n // 4, len(created))
    picked = rng.choice(created, n_created, replace=False)
    unused = np.uint64(plan["unused_ids"]) + rng.choice(
        1 << 20, n - n_created, replace=False).astype(np.uint64)
    return np.concatenate([picked, unused]).tolist()


def save_expected(path, setup_codes, window_codes, accounts, transfers):
    np.savez(path, accounts=accounts, transfers=transfers,
             codes=np.array(json.dumps(
                 {"setup": setup_codes, "window": window_codes})))


def load_expected(path) -> dict:
    with np.load(path) as z:
        codes = json.loads(str(z["codes"]))
        return {"accounts": z["accounts"], "transfers": z["transfers"],
                "setup": codes["setup"], "window": codes["window"]}


class ReferenceProcess:
    """`reference_child.py` as a child of the parent."""

    def __init__(self, root: str, mix_path: str, seed: int, env: dict):
        self.proc = subprocess.Popen(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "reference_child.py"),
             root, mix_path, str(seed)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=procs.child_env(env),
        )

    def _expect(self, word: str) -> float:
        line = self.proc.stdout.readline()
        if not line.startswith(word + " "):
            raise RuntimeError(
                f"reference process: expected {word!r}, got {line!r} "
                f"(rc={self.proc.poll()})")
        return float(line.split()[1])

    def wait_setup(self) -> float:
        """Seconds the reference took over the set-up's requests."""
        return self._expect("setup_done")

    def finish(self, counts: Sequence[int], out: str) -> float:
        self.proc.stdin.write(json.dumps(
            {"counts": list(counts), "out": out}) + "\n")
        self.proc.stdin.flush()
        seconds = self._expect("done")
        self.close()
        return seconds

    def close(self) -> None:
        """Its answer is in, or will not be read: end it and wait."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def _codes(pairs) -> list:
    return [[int(i), int(c)] for i, c in pairs]


def _rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Rows that differ in any field but `timestamp` (a missing or an extra
    row counts; rows are compared in the order of the lookup's ids)."""
    if len(got) != len(want):
        return max(len(got), len(want))
    names = [n for n in want.dtype.names if n != "timestamp"]
    differs = np.zeros(len(want), dtype=bool)
    for name in names:
        differs |= got[name] != want[name]
    return int(differs.sum())


def _bad_timestamps(rows: np.ndarray) -> int:
    stamps = rows["timestamp"]
    return int((stamps == 0).sum()) + len(stamps) - len(np.unique(stamps))


def replay_setup(ledger, plan: dict) -> dict:
    """The set-up's requests through the reference: {phase: codes of every
    request, per session}."""
    return {phase["name"]: [[ledger.execute(op, rows) for op, rows in queue]
                            for queue in phase["queues"]]
            for phase in plan["setup"]}


def replay_window(ledger, plan: dict, counts: Sequence[int]) -> list:
    """The window's answered requests, session by session (no result of
    these mixes depends on the order in which sessions' requests commit)."""
    return [[ledger.execute(op, rows) for op, rows in queue[:n]]
            for queue, n in zip(plan["window"], counts)]


def _wrong_codes(sent: list, per_session: list) -> Tuple[int, int]:
    """(requests whose codes differ from the reference's, requests)."""
    got = {(r.session, r.index): r for r in sent}
    wrong = total = 0
    for s, want_queue in enumerate(per_session):
        for k, want in enumerate(want_queue):
            r = got.get((s, k))
            total += 1
            wrong += r is None or r.codes is None or _codes(r.codes) != want
    return wrong, total


def compare(expected: dict, setup_sent: Dict[str, list], window_sent: list,
            got_accounts: np.ndarray, got_transfers: np.ndarray) -> dict:
    """{name: (value, limit)} for every number compared."""
    pairs = [_wrong_codes(setup_sent[name], per_session)
             for name, per_session in expected["setup"].items()]
    pairs.append(_wrong_codes(window_sent, expected["window"]))
    bad_codes, total = (sum(column) for column in zip(*pairs))
    return {
        "requests_compared": (total, None),
        "requests_with_wrong_codes": (bad_codes, 0),
        "account_rows_compared": (len(expected["accounts"]), None),
        "account_rows_differing": (
            _rows_differing(got_accounts, expected["accounts"]), 0),
        "transfer_rows_compared": (len(expected["transfers"]), None),
        "transfer_rows_differing": (
            _rows_differing(got_transfers, expected["transfers"]), 0),
        "rows_with_bad_timestamp": (
            _bad_timestamps(got_accounts) + _bad_timestamps(got_transfers),
            0),
    }


def verdict(numbers: dict) -> bool:
    return all(limit is None or value <= limit
               for value, limit in numbers.values())
