"""Network PS scaling: tokens/sec, 1 worker vs an elastic pool of 4.

One embedded ``PSServer`` per arm, real worker subprocesses
(``repro.ps.net.worker``) against it, dynamic lease assignment with one
deliberate straggler (``slow_ms``) in the pool arm -- the re-assignment
policy keeps the slow worker from bounding the run.  The localhost box
has no spare cores, so the pool's win comes from where a distributed
pool's win comes from: **overlapping network round-trips** -- every RPC
carries an emulated RTT (``TransportConfig.delay_ms``), serial for one
worker, hidden by concurrency for four.

Timing starts when the last worker registers (the server's start gate
releases ``acquire`` only then), so subprocess interpreter/jit start-up
skew -- serialised on this box, irrelevant on a real cluster -- stays
out of the tokens/sec numbers.

Gate: >= 1.5x tokens/sec going 1 -> 4 workers.  Writes
``experiments/bench/BENCH_net.json``.
"""
from __future__ import annotations

import json
import os
import tempfile
import time

OUT = "experiments/bench/BENCH_net.json"
DELAY_MS = 150.0                 # emulated per-RPC round-trip
STRAGGLER_SLOW_MS = 300.0        # extra per-visit latency for worker 0


def _run_arm(workers: int, *, epochs: int, corp, topics: int,
             shard_tokens: int, block_tokens: int) -> dict:
    import numpy as np

    from repro.api.session import init_stream
    from repro.core import lightlda as lda
    from repro.data import stream as stream_mod
    from repro.ps.client import PSClient
    from repro.ps.net import (NetClient, PSServer, WorkerConfig, WorkerPool,
                              wire)

    sdir = tempfile.mkdtemp(prefix=f"bench-net-{workers}w-")
    meta = stream_mod.write_sharded(sdir, corp, shard_tokens)
    reader = stream_mod.ShardedCorpusReader(sdir)
    cfg = lda.LDAConfig(num_topics=topics, vocab_size=meta.vocab_size,
                        block_tokens=block_tokens, num_shards=1)
    srv = PSServer(meta.vocab_size, topics, stream_dir=sdir).start()
    pool = None
    try:
        nwk0, nk0 = init_stream(reader, cfg, 0,
                                client=PSClient.create(num_shards=1))
        ctl = NetClient.connect(srv.address, name="bench-ctl", role="ctl")
        ctl.push_dense_prefix(wire.MAT_NWK, np.asarray(nwk0.to_dense()))
        ctl.push_dense_prefix(wire.MAT_NK, np.asarray(nk0.value))
        loader = stream_mod.StreamingLoader(reader, seed=0, prefetch=False)
        sched = loader.schedule(stream_mod.Cursor(0, 0), epochs)
        ctl.plan(sched, mode="dynamic", expected_workers=workers)

        base = WorkerConfig(server=srv.address, stream_dir=sdir,
                            num_topics=topics, block_tokens=block_tokens,
                            seed=0, commit_hot_rows=32, delay_ms=DELAY_MS)
        pool = WorkerPool(srv.address, base, env={"JAX_PLATFORMS": "cpu"})
        if workers > 1:
            pool.add_worker(slow_ms=STRAGGLER_SLOW_MS)   # the straggler
            pool.start(workers - 1)
        else:
            pool.start(1)

        # the start gate opens when the last worker says hello -- that is
        # the moment work can begin, so that is t0
        t_spawn = time.time()
        while True:
            st = ctl.status()
            joined = sum(1 for r in st["per_worker"].values()
                         if r["role"] == "worker")
            if joined >= workers:
                break
            if time.time() - t_spawn > 300:
                raise TimeoutError(f"workers never registered: {st}")
            time.sleep(0.05)
        t0 = time.time()
        pool.join(timeout=600)
        elapsed = time.time() - t0

        tokens = meta.num_tokens * epochs
        st = ctl.status()
        per_worker = {r["name"]: r["commits"]
                      for r in st["per_worker"].values()
                      if r["role"] == "worker"}
        return {"workers": workers, "visits": st["leases"]["done"],
                "elapsed_s": elapsed, "tokens": tokens,
                "tokens_per_s": tokens / elapsed,
                "commits_per_worker": per_worker,
                "startup_skew_s": t0 - t_spawn}
    finally:
        if pool is not None:
            pool.close()
        srv.stop()


def main(fast: bool = False):
    from repro.data import corpus as corpus_mod

    epochs = 2 if fast else 3
    corp = corpus_mod.generate_lda_corpus(
        seed=0, num_docs=160 if fast else 320, mean_doc_len=40,
        vocab_size=300, num_topics=6)

    arms = {}
    for n in (1, 4):
        arms[f"w{n}"] = _run_arm(n, epochs=epochs, corp=corp, topics=8,
                                 shard_tokens=1024, block_tokens=512)
        a = arms[f"w{n}"]
        print(f"net,workers={n},tokens_per_s={a['tokens_per_s']:.0f},"
              f"elapsed={a['elapsed_s']:.1f}s,visits={a['visits']},"
              f"commits={a['commits_per_worker']}")

    speedup = arms["w4"]["tokens_per_s"] / arms["w1"]["tokens_per_s"]
    print(f"net,speedup_1_to_4={speedup:.2f},rtt_ms={DELAY_MS:.0f},"
          f"straggler_slow_ms={STRAGGLER_SLOW_MS:.0f}")

    out = {"delay_ms": DELAY_MS, "straggler_slow_ms": STRAGGLER_SLOW_MS,
           "epochs": epochs, "arms": arms, "speedup_1_to_4": speedup}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(out, f, indent=2)
    print(f"net,artifact,{OUT}")

    assert speedup >= 1.5, \
        f"pool scaling gate: expected >= 1.5x tokens/s 1 -> 4 workers, " \
        f"got {speedup:.2f}x"
    # the straggler must not have been allowed to bound the run: with
    # dynamic assignment it works strictly fewer visits than the median
    commits = arms["w4"]["commits_per_worker"]
    straggler = commits.get("w0", 0)
    others = sorted(v for k, v in commits.items() if k != "w0")
    assert straggler <= others[len(others) // 2], commits
    return out


if __name__ == "__main__":
    main()
