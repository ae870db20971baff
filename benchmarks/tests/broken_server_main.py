"""Test-only launcher: the server with its timed path broken underneath.
An answer is altered where it is produced — the first account row of every
`lookup_accounts` reply carries one unit too many in `credits_posted` — and
the run's comparison must then come out as not correct."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import server_main  # noqa: E402


def main(argv) -> int:
    from tigerbeetle_tpu import machine

    sound = machine.TpuStateMachine.lookup_accounts

    def broken(self, ids):
        rows = sound(self, ids).copy()
        if len(rows):
            rows["credits_posted_lo"][0] += 1
        return rows

    machine.TpuStateMachine.lookup_accounts = broken
    return server_main.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
