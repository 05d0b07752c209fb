"""Mean requests per fold-in batch in the serving window: the mean of the
engine's ``serve.batch_occupancy`` histogram (reqs)."""


def read(run):
    return run.counters.get("batch_occupancy")
