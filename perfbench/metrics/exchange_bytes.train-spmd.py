"""Bytes each device puts into the sweep's collectives per sweep: the
program's count from the static shapes (``info["exchange_bytes_per_sweep"]``
of ``Session.make_step()``), summed over the snapshot all-gather, the hot
delta's psum, the cold COO all-gather and the n_k psum.  None where the
program gives no such count."""


def read(run):
    nbytes = run.counters.get("exchange_bytes_per_sweep")
    if not nbytes:
        return None
    return float(sum(nbytes.values()))
