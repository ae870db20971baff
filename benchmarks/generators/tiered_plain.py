"""Plain transfers that outlive the hot window, with clients that retry:
`ledger_mix`'s plain plan, and in the window some requests that re-send
events their session sent long ago.

A mix (`benchmarks/traffic/<name>.json`) gives `ledger_mix`'s parameters for a
cycle of one `plain` step (`accounts`, `batch`, `sessions`, `amount_max`,
`preload_per_session`, `window_cap_per_session`) and:

    retry_every      every this-many-th window request of a session is a retry
    retry_first      the first of them (position in the session's window queue)
    retry_events     its first this-many events are re-sent ones
    retry_sources    the k-th retry of a session re-sends the first
                     `retry_events` events of that session's preloaded request
                     `k mod retry_sources`, byte for byte

A retried event carries an id its session has had acknowledged (TigerBeetle's
guidance: retry a timed-out request under the same ids), so it is answered
`exists` (46) and writes nothing; the ids the request would have used in those
lanes are never created.  A deployment sized so that set-up fills the hot
window past its ceiling (`start --hot-transfers-log2-max`) has evicted those
old rows by the time the window opens: the retry is what asks the cold tier
for them.  A session re-sends only what it had acknowledged itself, and all
other ids are unique, so no result depends on the order in which sessions'
requests commit.
"""

from __future__ import annotations

from benchmarks.generators import ledger_mix

EXISTS = 46


def retry_positions(mix: dict) -> list:
    """Positions in a session's window queue that are retries."""
    return list(range(mix["retry_first"], mix["window_cap_per_session"],
                      mix["retry_every"]))


def build(mix: dict, seed: int) -> dict:
    """`ledger_mix.build`'s plan (same seed, same rows) with the retries
    written over the window's requests."""
    if mix["retry_sources"] > mix["preload_per_session"]:
        raise ValueError("a retry re-sends a request that was never preloaded")
    if mix["retry_events"] > mix["batch"]:
        raise ValueError("a retry re-sends more events than a request holds")
    plan = ledger_mix.build(dict(mix, cycle=["plain"]), seed)
    (preload,) = [p["queues"] for p in plan["setup"] if p["name"] == "preload"]
    events = mix["retry_events"]
    for sent, queue in zip(preload, plan["window"]):
        for k, at in enumerate(retry_positions(mix)):
            _op, source = sent[k % mix["retry_sources"]]
            operation, rows = queue[at]
            rows = rows.copy()
            rows[:events] = source[:events]
            queue[at] = (operation, rows)
    return plan
