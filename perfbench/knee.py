"""Find the knee of a serving cell: the highest open-loop rate it sustains.

    python3 perfbench/knee.py --workload <serving cell> --seed <n> \\
        --seconds <s> --rates 20 40 60 ...

One process sets the cell up once, then offers each rate for ``seconds``
and reports, per rate, the completions per second, the latency quantiles
(from when each request was due), how late the generator ran, the mean
batch occupancy and whether a backlog grew: the median latency of the
last third of the requests over that of the first third.  A rate is
sustained when completions keep up with the offered rate and that ratio
stays near 1.  The knee is written into the cell's traffic file by hand.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import harness
    import numpy as np
    import serve
    cell = harness.Cell(harness.load_spec(), args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import obs
    harness.device_info(cell.chips, True)
    harness.enable_compile_cache()
    cfg, tr = cell.config, cell.traffic
    n_max = int(round(max(args.rates) * args.seconds))
    docs, seeds, _ = serve.requests(cfg, tr, args.seed, n_max)
    nwk, nk, engine = serve.build(cell, args.seed)
    engine.start()
    rows = []
    import gen
    try:
        serve.warm(engine, docs, cfg["topics"])
        for rate in args.rates:
            n = int(round(rate * args.seconds))
            gaps = gen.arrival_gaps(n, rate, args.seed)
            session = obs.ObsSession(obs.ObsConfig(
                enabled=True, trace=False, metrics=True)).install()
            t_start, due, done, failed, _, late = serve.open_loop(
                engine, docs[:n], seeds[:n], gaps, args.seconds,
                tr["drain_s"])
            session.close(save=False)
            occ = session.metrics.histogram("serve.batch_occupancy")
            lat = (done - due) * 1e3
            third = max(n // 3, 1)
            row = {"rate": rate, "n": n,
                   "completed_per_s": float(np.sum(done <= t_start
                                                   + args.seconds))
                   / args.seconds,
                   "p50_ms": float(np.nanpercentile(lat, 50)),
                   "p95_ms": float(np.nanpercentile(lat, 95)),
                   "p99_ms": float(np.nanpercentile(lat, 99)),
                   "backlog_ratio": float(np.nanmedian(lat[-third:])
                                          / np.nanmedian(lat[:third])),
                   "late_ms": late * 1e3,
                   "occupancy": occ.total / max(occ.count, 1),
                   "lost": int(np.sum(np.isnan(done) | failed))}
            print(json.dumps(row), flush=True)
            rows.append(row)
            if (row["lost"] or row["completed_per_s"] < 0.8 * rate
                    or row["backlog_ratio"] > 3):
                break
    finally:
        engine.close(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
