"""Share of the traced training window in which no operation ran on the
device: 1 - busy / window, from the profiler trace (%)."""


def read(run):
    tr = run.trace
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
