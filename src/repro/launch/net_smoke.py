"""CI smoke for the network parameter server (DESIGN.md section 15).

    PYTHONPATH=src python -m repro.launch.net_smoke --workers 4

One self-contained localhost drill of everything the net plane promises:

  1. a **reference** single-process streamed run (``_StreamPlane``) on a
     copy of the corpus;
  2. a real ``repro.launch.ps_server`` subprocess + a ``WorkerPool`` of N
     worker subprocesses, every worker running with
     ``FaultInjector.once_per_op`` -- at least one forced retry for every
     op type it uses (hello / acquire / pull_full / commit);
  3. one worker **SIGKILLed mid-epoch**; the pool evicts it, its lease
     re-queues, survivors drain the schedule;
  4. asserts: exactly-once **count conservation** (server counts ==
     histogram of the on-disk z -- bitwise, despite retries and the
     kill), dedup acks observed, and final stream-wide perplexity within
     tolerance of the reference run.

Exit code 0 only if every assertion holds.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time


def run_smoke(workers: int = 4, epochs: int = 2, topics: int = 8,
              ppl_tol: float = 0.2, log=print) -> dict:
    import numpy as np

    from repro.api.session import _StreamPlane
    from repro.core import lightlda as lda
    from repro.core import perplexity as ppl
    from repro.data import corpus as corpus_mod
    from repro.data import stream as stream_mod
    from repro.api.session import init_stream
    from repro.ps.client import PSClient
    from repro.ps.net import NetClient, WorkerConfig, WorkerPool, wire
    from repro.train import async_exec

    corp = corpus_mod.generate_lda_corpus(seed=0, num_docs=160,
                                          mean_doc_len=40, vocab_size=300,
                                          num_topics=6)
    tmp = tempfile.mkdtemp(prefix="net-smoke-")
    ref_dir, net_dir = os.path.join(tmp, "ref"), os.path.join(tmp, "net")
    for d in (ref_dir, net_dir):
        stream_mod.write_sharded(d, corp, tokens_per_shard=1024)
    cfg = lda.LDAConfig(num_topics=topics, vocab_size=300,
                        block_tokens=512, num_shards=1)

    # -- 1. reference: single-process streamed run ------------------------
    log(f"[smoke] reference run: {epochs} epochs, single process")
    plane = _StreamPlane(ref_dir, cfg, async_exec.ExecConfig(), epochs,
                         seed=0, prefetch=False, log_fn=lambda *a: None)
    plane.setup()
    for visit in plane.schedule():
        plane.step(visit)
    ref_reader = stream_mod.ShardedCorpusReader(ref_dir)
    ref_ppl = ppl.stream_training_perplexity(
        ref_reader, np.asarray(plane.nwk.to_dense()),
        np.asarray(plane.nk.value), cfg.alpha, cfg.beta)
    log(f"[smoke] reference perplexity {ref_ppl:.2f}")

    # -- 2. real ps_server subprocess -------------------------------------
    ready = os.path.join(tmp, "ps.addr")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    srv_proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.ps_server",
         "--stream-dir", net_dir, "--topics", str(topics),
         "--ready-file", ready, "--quiet"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    t0 = time.time()
    while not os.path.exists(ready):
        if srv_proc.poll() is not None:
            raise RuntimeError("ps_server exited before binding")
        if time.time() - t0 > 30:
            raise TimeoutError("ps_server did not bind within 30s")
        time.sleep(0.05)
    with open(ready) as f:
        address = f.read().strip()
    log(f"[smoke] ps_server at {address} (pid {srv_proc.pid})")

    try:
        # seed the stream + load the initial counts
        reader = stream_mod.ShardedCorpusReader(net_dir)
        nwk0, nk0 = init_stream(reader, cfg, 0,
                                client=PSClient.create(num_shards=1))
        ctl = NetClient.connect(address, name="smoke-ctl", role="ctl")
        ctl.push_dense_prefix(wire.MAT_NWK, np.asarray(nwk0.to_dense()))
        ctl.push_dense_prefix(wire.MAT_NK, np.asarray(nk0.value))
        loader = stream_mod.StreamingLoader(reader, seed=0, prefetch=False)
        sched = loader.schedule(stream_mod.Cursor(0, 0), epochs)
        ctl.plan(sched, mode="dynamic", expected_workers=workers)

        # -- 3. worker pool, every worker under fault injection ------------
        base = WorkerConfig(server=address, stream_dir=net_dir,
                            num_topics=topics, block_tokens=512, seed=0,
                            commit_hot_rows=32, fault="once_per_op")
        pool = WorkerPool(address, base, env={"JAX_PLATFORMS": "cpu"},
                          log_fn=log)
        pool.start(workers)

        # wait until training is genuinely mid-flight, then SIGKILL one
        t0 = time.time()
        while True:
            st = ctl.status()
            done = (st.get("leases") or {}).get("done", 0)
            if done >= 2 and done < len(sched):
                break
            if done >= len(sched):
                log("[smoke] schedule drained before the kill window; "
                    "kill drill degraded to a no-op")
                break
            if time.time() - t0 > 300:
                raise TimeoutError(f"no progress for the kill window: {st}")
            time.sleep(0.1)
        pool.kill(0)
        status = pool.join(timeout=300)
        log(f"[smoke] final status: {json.dumps(status)}")

        # -- 4. the laws ---------------------------------------------------
        nwk = ctl.pull_full(wire.MAT_NWK)
        nk = ctl.pull_full(wire.MAT_NK)
        rw, rk = stream_mod.rebuild_counts_from_stream(reader, topics)
        assert np.array_equal(nwk, rw), \
            "conservation violated: server nwk != histogram(on-disk z)"
        assert np.array_equal(nk, rk), \
            "conservation violated: server nk != histogram(on-disk z)"
        assert int(nk.sum()) == corp.w.shape[0], \
            f"token mass changed: {int(nk.sum())} != {corp.w.shape[0]}"
        leases = status["leases"]
        assert leases["done"] == leases["total"], leases
        # every worker's injected faults forced >= 1 retry per op type
        # it used; the dedup cache must have answered the mutating ones
        assert status["dup_acks"] >= 1, status
        retries = [s.get("retries", 0) for s in pool.stats() if s]
        assert retries and all(r >= 3 for r in retries), \
            f"expected >= 3 forced retries per surviving worker " \
            f"(hello/acquire/pull_full/commit faulted once each): {retries}"

        net_ppl = ppl.stream_training_perplexity(reader, nwk, nk,
                                                 cfg.alpha, cfg.beta)
        rel = abs(net_ppl - ref_ppl) / ref_ppl
        log(f"[smoke] net perplexity {net_ppl:.2f} vs reference "
            f"{ref_ppl:.2f} (rel diff {rel:.3f})")
        assert rel < ppl_tol, \
            f"perplexity diverged: {net_ppl:.2f} vs {ref_ppl:.2f}"
        out = {"workers": workers, "visits": leases["total"],
               "reassigned": leases["reassigned"],
               "dup_acks": status["dup_acks"],
               "worker_retries": retries,
               "ref_perplexity": float(ref_ppl),
               "net_perplexity": float(net_ppl), "rel_diff": float(rel)}
        log(f"[smoke] PASS {json.dumps(out)}")
        return out
    finally:
        try:
            pool.close()
        except Exception:
            pass
        srv_proc.terminate()
        try:
            srv_proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            srv_proc.kill()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--topics", type=int, default=8)
    ap.add_argument("--ppl-tol", type=float, default=0.2)
    args = ap.parse_args(argv)
    run_smoke(workers=args.workers, epochs=args.epochs, topics=args.topics,
              ppl_tol=args.ppl_tol)
    return 0


if __name__ == "__main__":
    sys.exit(main())
