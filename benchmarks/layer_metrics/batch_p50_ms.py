"""Median (nearest rank) request->reply milliseconds of the window's answered
requests, host clock in the client.  In a closed loop it follows the
throughput by Little's law (sessions in flight / requests per second)."""

from benchmarks.harness.drive import latency_quantile_ms


def read(run):
    return latency_quantile_ms(run["window"], 0.50)
