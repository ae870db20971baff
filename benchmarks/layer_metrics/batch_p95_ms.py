"""95th percentile (nearest rank) of request->reply milliseconds of all the
window's answered requests, host clock in the client.  It steps by one
dispatch period whenever the share of slow commit cycles crosses 5 %, so it
is read here, beside the throughput, and carries no bound (PERF.md, PR 24)."""

from benchmarks.harness.drive import latency_quantile_ms


def read(run):
    return latency_quantile_ms(run["window"], 0.95)
