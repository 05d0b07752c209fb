"""Readings that set a cell's limits: sound runs, the control, the faults.

    python3 perfbench/control.py --workload <name> --seeds 1 2 3 \\
        [--seconds <s>]

For each seed, in one process, the cell's program is run as a benchmark
run drives it (training: set-up and one window sweep; serving: set-up and
``seconds`` of the cell's own load), and its ``correct`` numbers are read
four ways, one JSON line per seed:

* ``sound``: the program's output, as a benchmark run compares it;
* ``control``: the plain reference computed in bfloat16 put in the
  program's place (the nearest precision below the float32 the
  configuration states), at the same inputs;
* ``unchanged``, ``half``, ``token``: the program's output with a fault
  planted in it -- the state returned unchanged, half of the batch left
  unsampled, one token (or one answer) altered where it is produced.

The benchmark's own runs never run this; ``tests/test_checks.py`` runs it
at a size the test run can hold.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def train_readings(cell, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import train
    cfg = cell.config
    corp, state, step = train.build(cell, seed)
    keys = train.sweep_keys(seed, 2)
    state = step(state, keys[0])
    z_in = state.z
    state = step(state, keys[1])
    z_out = state.z
    jax.block_until_ready(z_out)
    out = {"seed": seed, "count_mismatch": train.conservation(cell, corp,
                                                              state)}
    del state, step
    n = z_in.shape[0]
    block = cfg["block_tokens"]
    pos = jnp.arange(n)
    planted = {
        "unchanged": z_in,
        "half": jnp.where(pos % 2 == 1, z_in, z_out),
        "token": jnp.where(pos % block == 0, (z_out + 1) % cfg["topics"],
                           z_out),
    }
    ref = train.check(cell, seed, corp, [(z_in, z_out, keys[1])],
                      control=True)
    out.update(checked=ref["checked"], ties=ref["ties"],
               sound=ref["mismatch"], control=ref["control"])
    for name, z in planted.items():
        out[name] = train.check(cell, seed, corp,
                                [(z_in, z, keys[1])])["mismatch"]
    return out


def serve_readings(cell, seed: int, seconds: int) -> dict:
    import numpy as np
    import serve
    cfg, tr = cell.config, cell.traffic
    n = int(round(tr["rate_per_s"] * seconds))
    docs, seeds, gaps = serve.requests(cfg, tr, seed, n)
    nwk, nk, engine = serve.build(cell, seed)
    engine.start()
    try:
        serve.warm(engine, docs, cfg["topics"])
        _, _, done, failed, thetas, _ = serve.open_loop(
            engine, docs, seeds, gaps, seconds, tr["drain_s"])
    finally:
        engine.close(drain=False)
    del engine
    out = {"seed": seed, "lost": int(np.sum(np.isnan(done) | failed))}
    ref = serve.check(cell, nwk, nk, docs, seeds, thetas, seed, control=True)
    out.update(checked=ref["checked"], ties=ref["ties"],
               sound=ref["mismatch"], control=ref["control"],
               widest_gap=ref["gap"])
    k = cfg["topics"]
    nd = np.array([min(len(d), tr["max_len"]) for d in docs], np.float32)
    prior = [np.full(k, cfg["alpha"], np.float32) / (x + k * cfg["alpha"])
             for x in nd]
    moved = []
    for j, th in enumerate(thetas):
        t = None if th is None else th.copy()
        if t is not None:
            step_ = 1.0 / ((tr["num_sweeps"] - tr["burnin"])
                           * (nd[j] + k * cfg["alpha"]))
            src = int(np.argmax(t))
            t[src] -= step_
            t[(src + 1) % k] += step_
        moved.append(t)
    planted = {
        "unchanged": prior,
        "half": [prior[j] if j % 2 else th for j, th in enumerate(thetas)],
        "token": moved,
    }
    for name, th in planted.items():
        out[name] = serve.check(cell, nwk, nk, docs, seeds, th,
                                seed)["mismatch"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=int, default=10)
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_spec(), args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.device_info(cell.chips, True)
    harness.enable_compile_cache()
    for seed in args.seeds:
        t = time.perf_counter()
        if cell.traffic["kind"] == "train":
            row = train_readings(cell, seed)
        else:
            row = serve_readings(cell, seed, args.seconds)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
