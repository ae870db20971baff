"""The control of `correct`: the reference put in the program's place with
ONE guarantee of the configuration broken.  Each control must come out as not
correct, and the sound run as correct.  (This system states no precision, so
the control breaks a guarantee: an acknowledged write that is lost, a read
that is stale, a resolution applied as the other kind.)

`test_control.py` runs it at a size a test run can hold.  At a cell's own
size, on the seeds given (no device is needed: the program is replaced):

    python benchmarks/tests/control.py <workload> <seed> [<seed> ...]
"""

import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import check, drive  # noqa: E402
from benchmarks.reference.ledger import ReferenceLedger, TF_POST, TF_VOID  # noqa: E402

CONTROLS = ["lost_acknowledged_write", "stale_read", "post_applied_as_void"]


def serve(plan, counts, seed, mix, fault=None):
    """A 'program' made of the reference: what it acknowledges and what it
    reads back, with `fault` put in at the last session's last request.
    Returns compare()'s arguments after `expected`."""
    led = ReferenceLedger()
    setup_sent, window_sent = {}, []
    for phase in plan["setup"]:
        setup_sent[phase["name"]] = [
            drive.Sent(s, k, op, len(rows), 0.0, 0.0, led.execute(op, rows))
            for s, queue in enumerate(phase["queues"])
            for k, (op, rows) in enumerate(queue)]
    stale = None
    for s, (queue, n) in enumerate(zip(plan["window"], counts)):
        for k, (op, rows) in enumerate(queue[:n]):
            last = s == len(counts) - 1 and k == n - 1
            if last and fault == "stale_read":
                stale = led.lookup_accounts(plan["account_ids"])
            if last and fault == "lost_acknowledged_write":
                codes = []            # acknowledged OK, never applied
            else:
                if last and fault == "post_applied_as_void":
                    rows = rows.copy()
                    posts = rows["flags"] == TF_POST
                    if posts.any():
                        rows["flags"][posts] = TF_VOID
                        rows["amount_lo"][posts] = 0
                    else:   # a request without posts: its last lane is lost
                        rows = rows[:-1]
                codes = led.execute(op, rows)
            window_sent.append(drive.Sent(s, k, op, len(rows), 0.0, 0.0,
                                          codes))
    ids = check.sample_transfer_ids(plan, counts, seed, mix)
    accounts = stale if stale is not None else led.lookup_accounts(
        plan["account_ids"])
    transfers = led.lookup_transfers(ids)
    stamp = 1                               # the server's clock
    for rows in (accounts, transfers):
        rows["timestamp"] = range(stamp, stamp + len(rows))
        stamp += len(rows)
    return setup_sent, window_sent, accounts, transfers


def expected(plan, counts, seed, mix, path):
    led = ReferenceLedger()
    setup = check.replay_setup(led, plan)
    window = check.replay_window(led, plan, counts)
    check.save_expected(
        path, setup, window, led.lookup_accounts(plan["account_ids"]),
        led.lookup_transfers(check.sample_transfer_ids(plan, counts, seed,
                                                       mix)))
    return check.load_expected(path)


def verdicts(mix, seed, counts, workdir) -> dict:
    """{"sound": numbers, <control>: numbers, ...} for one seed."""
    plan = check.load_generator(mix).build(mix, seed)
    want = expected(plan, counts, seed, mix,
                    os.path.join(workdir, "expected.npz"))
    return {fault or "sound": check.compare(
        want, *serve(plan, counts, seed, mix, fault))
        for fault in [None] + CONTROLS}


def main(argv) -> int:
    from benchmarks import run

    loaded = run.load_cell(ROOT, argv[0])
    mix = loaded["mix"]
    # As many requests as a full window can hold, the last session one
    # fewer: the fault then sits on the cycle's other step too.
    counts = [mix["window_cap_per_session"]] * mix["sessions"]
    counts[-1] -= 1
    ok = True
    with tempfile.TemporaryDirectory() as workdir:
        for seed in map(int, argv[1:]):
            for name, numbers in verdicts(mix, seed, counts, workdir).items():
                good = check.verdict(numbers) == (name == "sound")
                ok &= good
                print(json.dumps({
                    "workload": argv[0], "seed": seed, "run": name,
                    "correct": check.verdict(numbers), "as_it_must": good,
                    "numbers": {k: v for k, v in numbers.items()
                                if v[1] is not None}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
