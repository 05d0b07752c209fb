"""Median fold-in latency of the serving window, each request timed from
when it was due to when its theta was returned (ms): the steadier
statistic beside ``foldin_p95_ms``."""


def read(run):
    return run.counters["foldin_p50_ms"]
