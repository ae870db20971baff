"""Test-only launcher: the server with ONE account row altered where it is
produced: the last row of a `lookup_accounts` reply shorter than a full
message (the read-back's last request: the account with the highest id)
carries one unit too many in `debits_posted`.  A read-back that sampled the
accounts would miss it; the run's comparison must come out as not correct,
by one row."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import server_main  # noqa: E402

FULL_REPLY = 8190


def main(argv) -> int:
    from tigerbeetle_tpu import machine

    sound = machine.TpuStateMachine.lookup_accounts

    def broken(self, ids):
        rows = sound(self, ids).copy()
        if 0 < len(rows) < FULL_REPLY:
            rows["debits_posted_lo"][-1] += 1
        return rows

    machine.TpuStateMachine.lookup_accounts = broken
    return server_main.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
