"""Executables JAX obtained (compiled or read from its cache) inside the
measured training window; set-up warms every shape, so 0 is sound."""


def read(run):
    return run.counters["compiles_in_window"]
