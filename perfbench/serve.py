"""Serving cells: open-loop fold-in through ``ConcurrentEngine``.

Set-up makes the model's counts on the device from the seed (one jitted
call), publishes one snapshot through ``SnapshotPublisher`` (the program
builds its alias tables), starts a ``ConcurrentEngine`` over a
``QueryEngine`` with the program's defaults, generates the requests on the
host from the configuration's corpus generator, and warms every padding
bucket and batch occupancy the requests will use.

The window sends requests at the times the traffic file's arrival process
gives (open loop: a request is sent when it is due, whatever is still in
flight) and times each from when it was due until its theta is returned.
A request that fails counts as missing every limit.  After the window
closes the run waits up to ``drain_s`` for the requests still in flight.

``correct`` compares the theta of a sample of served requests, drawn from
the seed with the longest request in it, with the plain reference's
fold-in of the same tokens under the same per-request key
(``ref_lda.fold_in``): ``theta_mismatch`` counts the requests, ties left
out, whose theta differs anywhere by more than ``THETA_TOL``.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import gen
import harness
import ref_lda
import tracing
import work
from harness import log

# theta is a ratio of integer counts; one token's topic changed in one
# post-burn-in sweep moves theta by 1/((num_sweeps - burnin)(N_d + K alpha)),
# over 1e-5 for any document this engine admits, while f32 rounding of the
# same counts stays below 1e-7
THETA_TOL = 1e-6


def model_counts(cfg: dict, seed: int):
    """n_wk [V, K] and n_k [K] of a served model, made on the device from
    the seed: N tokens split over K topics by Dirichlet weights, each
    topic's words from a Dirichlet around the Zipf base, counts rounded
    stochastically from their expectations."""
    import jax
    import jax.numpy as jnp
    v, k = cfg["vocab"], cfg["topics"]
    gen_cfg = cfg["generator"]

    @jax.jit
    def make(key):
        k_phi, k_pi, k_r = jax.random.split(key, 3)
        base = 1.0 / jnp.arange(1, v + 1, dtype=jnp.float32) ** gen_cfg[
            "zipf_exponent"]
        base = base / base.sum()
        phi = jax.random.dirichlet(
            k_phi, base * gen_cfg["topic_concentration"], shape=(k,))
        pi = jax.random.dirichlet(
            k_pi, jnp.full((k,), cfg["model_topic_alpha"], jnp.float32))
        rate = (cfg["model_tokens"] * pi)[:, None] * phi            # [K, V]
        nwk = jnp.floor(rate + jax.random.uniform(k_r, rate.shape)
                        ).astype(jnp.int32).T
        return nwk, nwk.sum(axis=0)

    return make(jax.random.PRNGKey(int(gen.key_ints(seed, "model", 1)[0])))


def requests(cfg: dict, tr: dict, seed: int, n: int):
    """``n`` documents of the configuration's corpus shape (tokens and a
    fold-in seed each) and their arrival gaps."""
    corp = gen.config_corpus(cfg, seed, n, stream="requests")
    docs = [corp["w"][s:s + l] for s, l in zip(corp["doc_start"],
                                               corp["doc_len"])]
    seeds = [int(s) for s in gen.key_ints(seed, "keys", n)]
    return docs, seeds, gen.arrival_gaps(n, tr["rate_per_s"], seed)


def build(cell, seed: int):
    """Model, publisher and engine as the program serves them."""
    import jax
    from repro.core import lightlda as lda
    from repro.infer.engine import ConcurrentEngine, EngineConfig, QueryEngine
    from repro.infer.foldin import FoldInConfig
    from repro.infer.snapshot import SnapshotPublisher
    cfg, tr = cell.config, cell.traffic
    t = time.perf_counter()
    nwk, nk = model_counts(cfg, seed)
    jax.block_until_ready(nk)
    log(f"[setup] model counts: {int(nk.sum())} tokens over K={cfg['topics']}"
        f" in {time.perf_counter() - t:.3f} s")
    lcfg = lda.LDAConfig(num_topics=cfg["topics"], vocab_size=cfg["vocab"],
                         alpha=cfg["alpha"], beta=cfg["beta"],
                         mh_steps=cfg["mh_steps"], use_kernels=True)
    t = time.perf_counter()
    pub = SnapshotPublisher(lcfg)
    pub.publish(nwk, nk)
    log(f"[setup] snapshot published in {time.perf_counter() - t:.3f} s")
    ecfg = EngineConfig(
        max_batch=tr["max_batch"], min_bucket=tr["min_bucket"],
        max_len=tr["max_len"], max_delay_ms=tr["max_delay_ms"],
        deadline_ms=0.0,
        foldin=FoldInConfig(num_sweeps=tr["num_sweeps"], burnin=tr["burnin"],
                            use_kernels=True))
    engine = ConcurrentEngine(QueryEngine(pub, ecfg))
    return nwk, nk, engine


def warm(engine, docs, k: int) -> None:
    """Every bucket the requests use, and every batch occupancy of the
    result slice, run once before the window."""
    import jax.numpy as jnp
    import numpy as np
    qe = engine.engine
    one_per_bucket = {qe.bucket_of(max(min(len(d), qe.ecfg.max_len), 1)): d
                      for d in docs}
    buckets = sorted(one_per_bucket)
    t = time.perf_counter()
    for b in buckets:
        engine.submit(one_per_bucket[b], seed=0).result()
    mb = qe.ecfg.max_batch
    theta = jnp.zeros((mb, k), jnp.float32)
    for n in range(1, mb + 1):
        np.asarray(theta[:n])
    log(f"[setup] warmed buckets {buckets} and occupancies 1..{mb} in "
        f"{time.perf_counter() - t:.3f} s")


def open_loop(engine, docs, seeds, gaps, seconds: float, drain_s: float):
    """Send each request when due.  Returns (t_start, due, done, failed,
    thetas, late): times in perf_counter seconds, ``done`` NaN and theta
    None for a request that never completed, ``late`` the most the
    generator sent a request after it was due."""
    import numpy as np
    n = len(docs)
    thetas = [None] * n
    due_rel = np.cumsum(gaps)
    done = np.full(n, np.nan)
    failed = np.zeros(n, bool)
    tickets = {}
    late = 0.0
    i = 0
    t_start = time.perf_counter()
    due = t_start + due_rel
    deadline = t_start + seconds + drain_s
    while True:
        now = time.perf_counter()
        while i < n and due[i] <= now:
            late = max(late, now - due[i])
            tickets[i] = engine.submit(docs[i], seed=seeds[i])
            i += 1
        now = time.perf_counter()
        for j in [j for j, t in tickets.items() if t.done()]:
            t = tickets.pop(j)
            done[j] = now
            try:
                thetas[j] = t.result().theta
            except Exception as e:  # noqa: BLE001 -- a failed request
                failed[j] = True
                log(f"[window] request {j} failed: {e!r}")
        if i >= n and not tickets:
            break
        if now > deadline:
            log(f"[window] {len(tickets)} requests still in flight "
                f"{drain_s} s after the window closed")
            break
        nxt = due[i] if i < n else now + 0.0005
        time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.0005))
    log(f"[window] generator ran at most {late * 1e3:.3f} ms late")
    return t_start, due, done, failed, thetas, late


def check(cell, nwk, nk, docs, seeds, results, seed: int,
          control: bool = False) -> dict:
    """Reference fold-in of a seeded sample of served requests."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg, tr = cell.config, cell.traffic
    n = len(docs)
    sample = gen.rng(seed, "sample").choice(
        n, min(tr["check_requests"], n), replace=False)
    sample = np.unique(np.append(sample, int(np.argmax([len(d) for d in
                                                         docs]))))
    sample = [int(j) for j in sample if results[j] is not None]
    max_len, min_bucket = tr["max_len"], tr["min_bucket"]

    def bucket(m):
        b = min_bucket
        while b < m and b < max_len:
            b *= 2
        return min(b, max_len)

    toks = [np.asarray(docs[j][:max_len], np.int32) for j in sample]
    tab = ref_lda.build_tables(nwk, nk, np.concatenate(toks), cfg["beta"])
    log(f"[check] alias tables of {tab.prob.shape[0]} words: "
        f"{float(tab.unsure.mean()):.3e} of entries after a tied Vose test")
    kw = dict(num_topics=cfg["topics"], vocab_size=cfg["vocab"],
              mh_steps=cfg["mh_steps"], alpha=cfg["alpha"],
              beta=cfg["beta"], num_sweeps=tr["num_sweeps"],
              burnin=tr["burnin"])
    out = {"checked": 0, "ties": 0, "mismatch": 0, "control": 0,
           "gap": 0.0}
    groups = {}
    for j, t in zip(sample, toks):
        groups.setdefault(bucket(max(len(t), 1)), []).append((j, t))
    for l, items in sorted(groups.items()):
        w = np.zeros((len(items), l), np.int32)
        valid = np.zeros((len(items), l), bool)
        for r, (_, t) in enumerate(items):
            w[r, :len(t)] = t
            valid[r, :len(t)] = True
        keys = jnp.stack([jax.random.PRNGKey(seeds[j]) for j, _ in items])
        theta, tie = ref_lda.fold_in(jnp.asarray(w), jnp.asarray(valid),
                                     keys, nk, tab, **kw)
        theta, tie = np.asarray(theta), np.asarray(tie)
        got = np.stack([results[j] for j, _ in items])
        gap = np.abs(got - theta).max(axis=1)
        out["checked"] += int((~tie).sum())
        out["ties"] += int(tie.sum())
        out["mismatch"] += int(((gap > THETA_TOL) & ~tie).sum())
        out["gap"] = max(out["gap"], float(gap[~tie].max(initial=0.0)))
        if control:
            th_c, _ = ref_lda.fold_in(jnp.asarray(w), jnp.asarray(valid),
                                      keys, nk, tab, dtype=jnp.bfloat16,
                                      **kw)
            gap_c = np.abs(np.asarray(th_c) - theta).max(axis=1)
            out["control"] += int(((gap_c > THETA_TOL) & ~tie).sum())
    log(f"[check] reference: {out['checked']} requests compared, "
        f"{out['ties']} ties left out, {out['mismatch']} differ (widest "
        f"gap {out['gap']:.3e})")
    return out


def run(cell, *, seed: int, seconds: int, trace: bool, t0: float,
        peaks) -> dict:
    import jax
    import numpy as np
    from repro import obs
    cfg, tr = cell.config, cell.traffic
    n = int(round(tr["rate_per_s"] * seconds))
    docs, seeds, gaps = requests(cfg, tr, seed, n)
    nwk, nk, engine = build(cell, seed)
    session = None
    engine.start()
    try:
        warm(engine, docs, cfg["topics"])
        setup_s = time.perf_counter() - t0
        log(f"[setup] {setup_s:.3f} s to the window; {n} requests at "
            f"{tr['rate_per_s']}/s")
        if trace:
            session = obs.ObsSession(obs.ObsConfig(
                enabled=True, trace=False, metrics=True)).install()
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            jax.profiler.start_trace(trace_dir)
        compiles = harness.CompileCounter()
        compiles.counting = True
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            t_start, due, done, failed, thetas, late = open_loop(
                engine, docs, seeds, gaps, seconds, tr["drain_s"])
        compiles.counting = False
        if trace:
            jax.profiler.stop_trace()
    finally:
        engine.close(drain=False)
        if session is not None:
            session.close(save=False)
    peak = harness.memory_peak_bytes(cell.chips)
    lost = np.isnan(done) | failed
    lat_ms = np.where(lost, np.inf, (done - due) * 1e3)
    in_window = int(np.sum(done <= t_start + seconds))
    log(f"[window] {n} requests, {in_window} completed in the window, "
        f"{int(failed.sum())} failed, {int(np.isnan(done).sum())} never "
        f"completed; {compiles.count} compiles {compiles.names}")
    del engine
    ref = check(cell, nwk, nk, docs, seeds, thetas, seed)
    out = {"end_to_end": {"setup_s": setup_s,
                          "foldin_p95_ms": float(np.percentile(lat_ms, 95)),
                          "foldin_docs_per_s": in_window / seconds},
           "memory_peak_bytes": peak, "attempted": n,
           "failed": int(lost.sum()),
           "checks": {"theta_mismatch": {"value": ref["mismatch"],
                                         "limit": 0},
                      "requests_lost": {"value": int(lost.sum()),
                                        "limit": 0}}}
    if trace:
        summary = tracing.TraceSummary.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        occ = session.metrics.histogram("serve.batch_occupancy", unit="reqs")
        served_tokens = sum(min(len(docs[j]), tr["max_len"])
                            for j in range(n) if not lost[j])
        out["run"] = harness.Run(
            summary,
            counters={"compiles_in_window": compiles.count,
                      "batch_occupancy": (occ.total / occ.count
                                          if occ.count else None),
                      "foldin_p50_ms": float(np.percentile(lat_ms, 50)),
                      "generator_late_ms": late * 1e3},
            work={"mh_sample": work.mh_sample(
                served_tokens * tr["num_sweeps"], cfg["mh_steps"],
                frozen=True)},
            peaks=peaks)
    return out
