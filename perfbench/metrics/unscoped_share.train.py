"""Share of the traced training window's device operation time spent in
operations that the program places in no phase of the sweep (%).

The program compiles the sweep's phases under ``jax.named_scope`` and
gives out, through ``repro.obs.scopes.scope_table()``, the phase of each
compiled instruction.  ``phase_seconds`` here turns the trace's seconds
per operation into seconds per phase; the ``*_s.train`` readers load it
from this file.
"""
import sys

import tracing

# operation time the table does not know, or places ambiguously, beyond
# this share of the window's operation time means the table is not the
# program that ran: nothing is read
UNMATCHED_LIMIT = 0.01


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def phase_seconds(run):
    """``{phase or None: seconds}`` of the window's operations, or None
    where the program gives out no scope table or more than 1% of the
    operation time is unmatched."""
    try:
        from repro.obs import scopes
    except ImportError:
        log("[phases] the program has no repro.obs.scopes")
        return None
    table = scopes.scope_table()
    out, unmatched = {}, {}
    for op, s in run.trace.ops().items():
        m = tracing.HLO.match(op)
        phase = table.get(m.group(1)[1:], scopes.AMBIGUOUS) if m \
            else scopes.AMBIGUOUS
        if phase == scopes.AMBIGUOUS:
            unmatched[op] = s
        else:
            out[phase] = out.get(phase, 0.0) + s
    total = sum(out.values()) + sum(unmatched.values())
    if total <= 0:
        log("[phases] no operation time in the window")
        return None
    if sum(unmatched.values()) > UNMATCHED_LIMIT * total:
        top = sorted(unmatched.items(), key=lambda kv: -kv[1])[:10]
        log(f"[phases] {sum(unmatched.values()):.6f} s of {total:.6f} s in "
            f"operations the scope table of {scopes.registered()} does not "
            f"place: {[(tracing.short(k), v) for k, v in top]}")
        return None
    log(f"[phases] seconds in the window: "
        f"{ {k: round(v, 6) for k, v in out.items()} }, unmatched "
        f"{sum(unmatched.values()):.6f} s")
    return out


def read(run):
    seconds = phase_seconds(run)
    if seconds is None:
        return None
    return 100.0 * seconds.get(None, 0.0) / sum(run.trace.ops().values())
