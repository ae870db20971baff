"""The plain reference in a process of its own, beside the set-up.

    python reference_child.py <benchmarks root> <mix.json> <seed>

Builds the same plan from the same seed as the parent, replays the set-up
through `benchmarks/reference/ledger.py` while the server is being set up,
prints `setup_done`, and then blocks on stdin — so it is idle while the
window is open.  After the window the parent sends one JSON line
`{"counts": [requests each session had answered], "out": path}`; the child
replays those requests, draws the same sample of transfer ids as the parent,
and writes what the server must have answered.
"""

from __future__ import annotations

import json
import sys
import time


def main(argv) -> int:
    root, mix_path, seed = argv[0], argv[1], int(argv[2])
    sys.path.insert(0, root)
    from benchmarks.harness import check, procs

    procs.die_with_parent()
    from benchmarks.reference.ledger import ReferenceLedger

    with open(mix_path) as f:
        mix = json.load(f)
    plan = check.load_generator(mix).build(mix, seed)
    ledger = ReferenceLedger()
    t0 = time.monotonic()
    setup_codes = check.replay_setup(ledger, plan)
    print(f"setup_done {time.monotonic() - t0:.3f}", flush=True)
    line = sys.stdin.readline()
    if not line:
        return 1  # the parent went away
    ask = json.loads(line)
    t0 = time.monotonic()
    window_codes = check.replay_window(ledger, plan, ask["counts"])
    ids = check.sample_transfer_ids(plan, ask["counts"], seed, mix)
    check.save_expected(
        ask["out"], setup_codes, window_codes,
        ledger.lookup_accounts(plan["account_ids"]),
        ledger.lookup_transfers(ids),
    )
    print(f"done {time.monotonic() - t0:.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
