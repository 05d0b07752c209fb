"""Device seconds per sweep of the traced training window in the operations
compiled under the program's ``alias.tables`` scope: the proposal
weights and the alias-table build (the kernel and its argsorts). None
where the program has no such scope or its scope table does not match
the trace (``unscoped_share.train.phase_seconds``)."""
import importlib.util
import os

PHASE = "alias.tables"


def read(run):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "unscoped_share.train.py")
    spec = importlib.util.spec_from_file_location("perfbench_phases", path)
    phases = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(phases)
    seconds = phases.phase_seconds(run)
    if seconds is None or PHASE not in seconds:
        return None
    return seconds[PHASE] / run.counters["sweeps"]
