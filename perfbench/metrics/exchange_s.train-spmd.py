"""Device seconds per sweep of the traced training window in the operations
the program compiles under its ``exchange`` scope: the collectives between
devices (the snapshot all-gather of the servers' n_wk rows, the psum of the
hot delta and of n_k, the all-gather of the cold COO buffers), averaged over
the device planes.  None where the program's scope table has no
``exchange`` or does not match the trace (more than 1% of operation time
unknown to it)."""
import sys

import tracing

UNMATCHED_LIMIT = 0.01


def read(run):
    try:
        from repro.obs import scopes
        table = scopes.scope_table(exchange=True)
    except (ImportError, TypeError) as e:
        print(f"[exchange] the program has no exchange scope table: {e}",
              file=sys.stderr, flush=True)
        return None
    under, unmatched, total = 0.0, 0.0, 0.0
    for op, s in run.trace.ops().items():
        m = tracing.HLO.match(op)
        entry = table.get(m.group(1)[1:], scopes.AMBIGUOUS) if m \
            else scopes.AMBIGUOUS
        total += s
        if entry == scopes.AMBIGUOUS:
            unmatched += s
        elif entry[1]:
            under += s
    if total <= 0 or unmatched > UNMATCHED_LIMIT * total:
        print(f"[exchange] {unmatched:.6f} s of {total:.6f} s unmatched",
              file=sys.stderr, flush=True)
        return None
    print(f"[exchange] {under:.6f} s under exchange in the window",
          file=sys.stderr, flush=True)
    return under / run.counters["sweeps"]
