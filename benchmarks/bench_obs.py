"""Telemetry-plane benchmark: disabled-mode overhead + a fully traced run.

Two halves:

1. **Overhead bar.**  The executor's obs wrapper (``_obs_step``) must be
   free when no session is installed: per sweep it costs one module
   attribute read and one ``is None`` test.  This bench times the wrapped
   step against the unwrapped ``step.raw`` on the same state/keys and
   asserts the overhead is **< 1%** (best-of-repeats on both sides).

2. **Traced demo.**  One obs session covering the whole lifecycle --
   api-session training (exec.sweep / exec.dispatch spans), one
   ``MatrixHandle.push`` per route (dense / coo / hybrid ps.push spans),
   and a ``QueryEngine`` flush (serve.request_ms p50/p99).  The resulting
   ``trace.json`` is Perfetto-loadable; the bench prints the
   ``obs_report`` summary of the very same directory and asserts every
   section materialised.

Writes ``experiments/bench/BENCH_obs.json``.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, obs, ps
from repro.data import corpus as corpus_mod
from repro.infer.engine import EngineConfig, QueryEngine
from repro.infer.foldin import FoldInConfig
from repro.launch import obs_report
from repro.obs import time_loop
from repro.train import async_exec

OUT = "experiments/bench/BENCH_obs.json"
OBS_DIR = "experiments/bench/obs_demo"


def _setup(num_docs, vocab, k, shards, seed=0):
    corp = corpus_mod.synthetic_corpus(num_docs, vocab, model_topics=k,
                                       mean_doc_len=60, seed=seed)
    job = api.LDAJob(corpus=corp, num_topics=k, num_shards=shards,
                     sweeps=1, eval_every=0, seed=seed)
    sess = api.Session(job, log_fn=lambda *a, **kw: None)
    state, _, _ = sess.make_step()
    return corp, sess.cfg, state


def _ms_per_sweep(step, state, iters, repeats, label):
    _, tm = time_loop(lambda st, g: step(st, jax.random.PRNGKey(1 + g)),
                      state, iters, repeats=repeats, sync=lambda st: st.z,
                      label=label)
    return tm.ms_per_iter()


def main(fast: bool = False):
    num_docs, vocab, k, blocks = ((600, 1000, 32, 8) if fast
                                  else (2000, 4000, 64, 16))
    iters, repeats = (4, 3) if fast else (3, 4)
    corp, cfg, state = _setup(num_docs, vocab, k, shards=blocks)
    print(f"obs,corpus,{corp.num_tokens},tokens,V={vocab},K={k}")

    # --- 1. disabled-mode overhead: wrapped step vs step.raw -------------
    # interleave the two measurements (raw, wrapped, raw, wrapped, ...)
    # and keep the best of each, so clock drift / background load hits
    # both sides equally instead of whichever ran second
    ecfg = async_exec.ExecConfig(staleness=2, model_blocks=blocks)
    step, info = async_exec.make_executor(state, cfg, ecfg)
    assert obs.active() is None, "an obs session is already installed"
    raw_ms = wrapped_ms = float("inf")
    for r in range(repeats):
        raw_ms = min(raw_ms, _ms_per_sweep(step.raw, state, iters, 1,
                                           "obs_raw"))
        wrapped_ms = min(wrapped_ms, _ms_per_sweep(step, state, iters, 1,
                                                   "obs_wrapped"))
    overhead_pct = (wrapped_ms - raw_ms) / raw_ms * 100.0
    print(f"obs,overhead_disabled,{raw_ms:.2f},raw_ms,"
          f"{wrapped_ms:.2f},wrapped_ms,{overhead_pct:+.3f},pct")

    # --- 2. traced demo: one session over train + push + serve -----------
    obs_cfg = obs.ObsConfig(enabled=True, out_dir=OBS_DIR)
    with obs.session(obs_cfg):
        # training through the api session; ExecConfig.obs=None inherits
        # the installed session, so exec.sweep spans land here
        job = api.LDAJob(corpus=corp, num_topics=k, num_shards=blocks,
                         staleness=2, model_blocks=blocks,
                         sweeps=iters, eval_every=0, seed=0)
        model = api.APSLDA(job, log_fn=lambda *a, **kw: None).fit()

        # one eager push per route: the per-route ps.push cost table
        client = ps.PSClient.create(num_shards=4)
        base = client.matrix(cfg.V, cfg.K)
        rng = np.random.default_rng(0)
        batch = 4096
        w = jnp.asarray(rng.integers(0, cfg.V, size=batch, dtype=np.int32))
        re = ps.Reassign(
            rows=w, words=w,
            z_old=jnp.asarray(rng.integers(0, k, batch, dtype=np.int32)),
            z_new=jnp.asarray(rng.integers(0, k, batch, dtype=np.int32)),
            changed=jnp.asarray(rng.random(batch) < 0.6))
        for route in (ps.DenseRoute(), ps.CooRoute(),
                      ps.HybridRoute(hot_words=max(cfg.V // 8, 1))):
            base.with_route(route).push(re)

        # serving: engine flush -> serve.request_ms / batch occupancy
        eng = QueryEngine(model.publisher(),
                          EngineConfig(max_batch=16,
                                       foldin=FoldInConfig(num_sweeps=4,
                                                           burnin=2)))
        docs = [rng.integers(0, vocab, size=int(n)).astype(np.int32)
                for n in rng.integers(8, 64, size=24)]
        for d in docs:
            eng.submit(d)
        eng.flush()

    # --- report + acceptance ---------------------------------------------
    report = obs_report.render(OBS_DIR)
    print(report)

    events = obs_report.load_trace(os.path.join(OBS_DIR, "trace.json"))
    names = {ev["name"] for ev in events if ev.get("ph") == "X"}
    for needed in ("exec.sweep", "exec.dispatch", "ps.push", "engine.flush"):
        assert needed in names, f"traced demo missing {needed!r} spans"
    route_labels = {ev["args"]["route"] for ev in events
                    if ev.get("ph") == "X" and ev["name"] == "ps.push"}
    assert {"dense", "coo", "hybrid"} <= route_labels, route_labels
    assert "serve.request_ms" in report, "serving latency section missing"
    print(f"obs,traced_demo,{len(events)},events,"
          f"{sorted(route_labels)},routes")

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump({
            "config": {"tokens": corp.num_tokens, "V": vocab, "K": k,
                       "model_blocks": blocks, "iters": iters,
                       "repeats": repeats},
            "raw_ms_per_sweep": raw_ms,
            "wrapped_ms_per_sweep": wrapped_ms,
            "disabled_overhead_pct": overhead_pct,
            "trace_events": len(events),
            "trace_dir": OBS_DIR,
        }, f, indent=2)
    print(f"obs,wrote,{OUT}")
    assert overhead_pct < 1.0, (
        f"disabled-mode obs overhead {overhead_pct:.2f}% >= 1%")


if __name__ == "__main__":
    main(fast=True)
