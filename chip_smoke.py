#!/usr/bin/env python3
"""Chip smoke: APS-LDA training and fold-in serving on a TPU at NYTimes widths.

Drives the main path once through the entry points a user calls --
``repro.api.LDAJob`` / ``APSLDA(job).fit()``, then
``TopicModel.publisher()`` and ``QueryEngine`` fold-in -- on a synthetic
corpus of the UCI bag-of-words NYTimes shape: D=299,752 documents,
V=102,660 words, mean length 332 (about 99.5M tokens), K=1,024 topics.

    python3 chip_smoke.py                 # one chip, phases a-f
    python3 chip_smoke.py --four-chips    # four chips: the SPMD plane and
                                          # the one-chip run it is held to
                                          # (both on the Pallas path)

Phases (one chip): a. device, b. corpus, c. training on the jnp path,
d. training on the Pallas path, e. serving with both paths, f. result.
Every phase prints its seconds and each check on a line of its own; a
failed check exits non-zero.  The last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  With no TPU
the script exits non-zero and prints no result.

``--docs N`` sets D, 30,000 unless given (printed as ``reduced: D 299752
-> N``); V and K are never cut.  The compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# UCI bag-of-words NYTimes (docword.nytimes): documents, vocabulary, and
# tokens / documents; K is the topic count of ROADMAP's first deployment.
NYTIMES = {"docs": 299_752, "vocab": 102_660, "mean_doc_len": 332,
           "topics": 1024}
HOT_WORDS = 2000      # paper section 3.3: the 2000 hottest words dense
# Generative topics of the synthetic corpus.  With hundreds of sparse
# topics three sweeps from a random start leave the model no better than
# the unigram on held-out documents; sixteen broad ones are learnt
# measurably in three sweeps, which is what the held-out check asks.
GEN_TOPICS = 16
HELDOUT_DOCS = 1000   # held-out documents for fold-in perplexity
SERVE_DOCS = 64       # held-out documents folded in by the serving phase
PPL_AGREE = 0.01      # jnp vs Pallas perplexity, when z differs
PPL_AGREE_SPMD = 0.02  # four-chip SPMD vs one-chip perplexity
# D is cut by default: a sweep samples ~0.8M tokens/s (Pallas) or ~0.14M
# (jnp, plus its 57 s alias build) on a v5e, so three sweeps of each path
# over all 99.5M tokens would take about 14 minutes on their own.
DOCS = 30_000


class SmokeFailed(RuntimeError):
    pass


class Report:
    """Prints phase timings and checks; remembers whether all held."""

    def __init__(self):
        self.failed = []

    def phase(self, name: str, seconds: float):
        print(f"[{name}] seconds {seconds:.3f}", flush=True)

    def check(self, phase: str, name: str, ok: bool, detail: str = ""):
        print(f"[{phase}] check {name}: {'PASS' if ok else 'FAIL'}"
              f"{'  ' + detail if detail else ''}", flush=True)
        if not ok:
            self.failed.append(f"{phase}: {name}")


def eval_callback(heldout):
    """The repo's ``EvalCallback`` after every sweep -- training
    perplexity plus held-out fold-in perplexity.  It waits for each sweep
    to finish before its own clock starts, so ``sweep_s`` holds each
    sweep's seconds (dispatch to done), ``sweep_compiles`` the programs
    JAX compiled (or read from its cache) in each, and ``seconds``
    evaluation's."""
    import jax

    from repro import api
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _, **kw: compiles.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)

    class TimedEval(api.EvalCallback):
        seconds = 0.0

        def on_fit_start(self, info):
            self.sweep_s = []
            self.sweep_compiles = []
            self.mark = time.perf_counter()
            self.seen = len(compiles)

        def on_sweep_end(self, view):
            view.sync()
            t0 = time.perf_counter()
            self.sweep_s.append(t0 - self.mark)
            self.sweep_compiles.append(len(compiles) - self.seen)
            super().on_sweep_end(view)
            self.mark = time.perf_counter()
            self.seen = len(compiles)
            self.seconds += self.mark - t0

    return TimedEval(every=1, include_last=False, heldout=heldout,
                     log_fn=lambda line: print(" ", line, flush=True))


def conservation(rep: Report, phase: str, state, nwk, nk, cfg):
    """PS counts == histograms of the assignments, exactly."""
    import jax.numpy as jnp
    one = state.valid.astype(jnp.int32)
    hist = jnp.zeros((cfg.V, cfg.K), jnp.int32).at[state.w, state.z].add(one)
    rep.check(phase, "nwk == (w, z) histogram",
              bool(jnp.array_equal(hist, nwk.to_dense())))
    del hist
    nk_hist = jnp.zeros((cfg.K,), jnp.int32).at[state.z].add(one)
    rep.check(phase, "nk == bincount(z)",
              bool(jnp.array_equal(nk_hist, nk.pull_all().result())),
              f"sum {int(nk_hist.sum())} tokens")
    ndk = jnp.zeros(state.ndk.shape, jnp.int32).at[state.d, state.z].add(one)
    rep.check(phase, "ndk == (d, z) histogram",
              bool(jnp.array_equal(ndk, state.ndk)))


def perplexities(rep: Report, phase: str, history):
    """Both perplexities finite; training perplexity falls every sweep,
    held-out fold-in perplexity from the first sweep to the last."""
    import math
    train_p = [row["perplexity"] for row in history]
    held_p = [row["heldout_perplexity"] for row in history]
    rep.check(phase, "perplexities finite",
              all(math.isfinite(v) for v in train_p + held_p))
    rep.check(phase, "training perplexity falls every sweep",
              len(train_p) >= 2
              and all(b < a for a, b in zip(train_p, train_p[1:])),
              " -> ".join(f"{v:.4f}" for v in train_p))
    rep.check(phase, "held-out perplexity falls across the sweeps",
              len(held_p) >= 2 and held_p[-1] < held_p[0],
              " -> ".join(f"{v:.4f}" for v in held_p))
    return held_p


def train(rep: Report, phase: str, job, heldout):
    """``APSLDA(job).fit()`` with evaluation after every sweep; then the
    conservation checks.  Returns (model, result, held-out perplexities,
    programs compiled per sweep)."""
    from repro import api
    est = api.APSLDA(job)
    ev = eval_callback(heldout)
    t0 = time.perf_counter()
    model = est.fit(callbacks=[ev])
    fit_s = time.perf_counter() - t0
    res = est.result_
    print(f"[{phase}] executor {res.info}", flush=True)
    ntok = int(res.state.valid.sum())
    sweeps = " ".join(f"{t:.3f}" for t in ev.sweep_s)
    setup_s = fit_s - ev.seconds - sum(ev.sweep_s)
    print(f"[{phase}] fit seconds {fit_s:.3f}: set-up {setup_s:.3f}, "
          f"evaluation {ev.seconds:.3f}, sweeps {sweeps} (the first "
          f"includes compilation; {ntok} tokens per sweep, "
          f"{ntok / ev.sweep_s[-1]:.1f} tokens/s in the last); programs "
          f"compiled per sweep {ev.sweep_compiles}", flush=True)
    conservation(rep, phase, res.state, res.nwk, res.nk, model.cfg)
    held_p = perplexities(rep, phase, ev.history)
    return model, res, held_p, ev.sweep_compiles


def make_corpus(rep: Report, args):
    from repro.data import corpus as corpus_mod
    t0 = time.perf_counter()
    docs = args.docs
    if docs != NYTIMES["docs"]:
        print(f"reduced: D {NYTIMES['docs']} -> {docs}", flush=True)
    corp = corpus_mod.synthetic_corpus(
        docs, NYTIMES["vocab"], true_topics=GEN_TOPICS,
        mean_doc_len=NYTIMES["mean_doc_len"], seed=args.seed)
    gen_s = time.perf_counter() - t0
    train_c, held = corpus_mod.train_heldout_split(
        corp, heldout_frac=HELDOUT_DOCS / docs, seed=args.seed + 1)
    print(f"[b] corpus: D={corp.num_docs} V={corp.vocab_size} "
          f"N={corp.num_tokens} tokens (mean length "
          f"{corp.num_tokens / corp.num_docs:.1f}); generated in "
          f"{gen_s:.3f}s", flush=True)
    print(f"[b] train {train_c.num_docs} docs / {train_c.num_tokens} "
          f"tokens; held out {held.num_docs} docs / {held.num_tokens} "
          f"tokens", flush=True)
    print(f"[b] hottest word: {corp.word_freq[0] / corp.num_tokens:.4f} of "
          f"tokens", flush=True)
    rep.check("b", "widths", corp.vocab_size == NYTIMES["vocab"]
              and held.num_docs >= SERVE_DOCS,
              f"V={corp.vocab_size} K={NYTIMES['topics']}")
    rep.phase("b", time.perf_counter() - t0)
    return train_c, held


def base_job(args, train_c, **kw):
    from repro import api
    return api.LDAJob(corpus=train_c, num_topics=NYTIMES["topics"],
                      vocab_size=NYTIMES["vocab"], sweeps=args.sweeps,
                      block_tokens=args.block_tokens, eval_every=0,
                      seed=args.seed,
                      route=api.HybridRoute(hot_words=HOT_WORDS,
                                            use_kernel=False), **kw)


def phase_c(rep, args, train_c, held, use_kernels: bool = False):
    """One-chip training; the jnp path unless ``use_kernels`` (the
    four-chip run's reference, where the jnp alias build's ~57 s per
    sweep would dominate the four-chip bill -- phase d of the one-chip
    run shows the Pallas path bitwise equal to the jnp path)."""
    t0 = time.perf_counter()
    job = base_job(args, train_c, use_kernels=use_kernels)
    print(f"[c] {'Pallas' if use_kernels else 'jnp'} path: snapshot "
          f"executor, block_tokens {job.block_tokens}, route {job.route}",
          flush=True)
    model, res, held_p, _ = train(rep, "c", job, held)
    rep.phase("c", time.perf_counter() - t0)
    return model, res.state.z, held_p


def phase_d(rep, args, train_c, held, ref):
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.kernels import ops as kops
    t0 = time.perf_counter()
    job = base_job(args, train_c, use_kernels=True)
    print(f"[d] Pallas path: snapshot executor, block_tokens "
          f"{job.block_tokens}, route {job.route}: the dense push "
          f"spans the {HOT_WORDS} hottest rows, the cold tail is "
          f"scattered; model_blocks {job.model_blocks} (a blocked sweep "
          f"gathers a whole model block's tokens at once, and the "
          f"hottest word alone holds about a tenth of them)", flush=True)
    interpret = kops.default_interpret()
    rep.check("d", "Pallas interpret mode resolved to False",
              interpret is False, f"default_interpret() = {interpret}")
    session = api.Session(job, log_fn=lambda *a: None)
    state, step, _ = session.make_step()
    hlo = step.raw.lower(state, jax.random.PRNGKey(0)).as_text()
    # one custom call per kernel: alias build, MH sampler, hot-row push
    rep.check("d", "training step lowers to tpu_custom_call",
              hlo.count("tpu_custom_call") >= 3,
              f"{hlo.count('tpu_custom_call')} occurrences")
    del session, state, step, hlo
    model, res, values, _ = train(rep, "d", job, held)

    model_c, z_c, values_c = ref
    valid = res.state.valid
    differ = int(jnp.sum((res.state.z != z_c) & valid))
    ntok = int(valid.sum())
    equal = differ == 0
    print(f"[d] z bitwise equal to phase c: {'yes' if equal else 'no'} "
          f"({differ} of {ntok} tokens differ, share "
          f"{differ / ntok:.6f})", flush=True)
    gap = abs(values[-1] - values_c[-1]) / values_c[-1]
    rep.check("d", "agrees with phase c",
              equal or gap < PPL_AGREE,
              f"final held-out perplexity {values[-1]:.4f} vs "
              f"{values_c[-1]:.4f} (relative gap {gap:.6f}, limit "
              f"{PPL_AGREE})")
    rep.phase("d", time.perf_counter() - t0)
    return model


def serve(rep, label: str, model, held, use_kernels: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.infer.engine import EngineConfig, QueryEngine
    from repro.infer.foldin import FoldInConfig, fold_in_batch, pack_docs
    t0 = time.perf_counter()
    pub = model.publisher()
    snap = pub.acquire()
    jax.block_until_ready(snap.model.aprob)
    pub_s = time.perf_counter() - t0
    fcfg = FoldInConfig(use_kernels=use_kernels)
    eng = QueryEngine(pub, EngineConfig(foldin=fcfg))
    docs = [np.asarray(held.w[s:s + n]) for s, n in
            zip(held.doc_start[:SERVE_DOCS], held.doc_len[:SERVE_DOCS])]
    if use_kernels:
        w, valid = pack_docs(docs[:eng.ecfg.max_batch],
                             eng.bucket_of(max(map(len, docs))))
        keys = jnp.stack([jax.random.PRNGKey(i) for i in range(len(w))])
        hlo = fold_in_batch.lower(snap.model, w, valid, keys, snap.cfg,
                                  fcfg).as_text()
        rep.check("e", f"{label}: fold-in lowers to tpu_custom_call",
                  "tpu_custom_call" in hlo)
    t1 = time.perf_counter()
    results = eng.infer(docs, seeds=list(range(len(docs))))
    theta = np.stack([r.theta for r in results])
    fold_s = time.perf_counter() - t1
    queries = [d[:8] for d in docs[:4]]
    t2 = time.perf_counter()
    scores = eng.score(results, docs, queries)
    score_s = time.perf_counter() - t2
    print(f"[e] {label}: publish {pub_s:.3f}s (alias tables "
          f"{'Pallas' if model.cfg.use_kernels else 'jnp'}), fold-in of "
          f"{len(docs)} docs {fold_s:.3f}s, scoring {len(queries)} queries "
          f"{score_s:.3f}s (compilation included)", flush=True)
    dev = float(np.abs(theta.sum(axis=1) - 1.0).max())
    rep.check("e", f"{label}: theta rows sum to 1",
              theta.shape == (len(docs), model.num_topics) and dev < 1e-3,
              f"shape {theta.shape}, max |sum - 1| {dev:.2e}")
    rep.check("e", f"{label}: scores finite",
              scores.shape == (len(queries), len(docs))
              and bool(np.isfinite(scores).all()),
              f"shape {scores.shape}")
    # the query's own document should rank first on average
    top = (scores.argmax(axis=1) == np.arange(len(queries))).mean()
    print(f"[e] {label}: queries ranked their source doc first "
          f"{top:.2f} of the time", flush=True)
    return theta


def phase_e(rep, model_c, model_d, held):
    import numpy as np
    t0 = time.perf_counter()
    th_c = serve(rep, "jnp", model_c, held, use_kernels=False)
    th_d = serve(rep, "Pallas", model_d, held, use_kernels=True)
    print(f"[e] theta, jnp vs Pallas serving (the models of phases c and "
          f"d): max |diff| {float(np.abs(th_c - th_d).max()):.3e}",
          flush=True)
    rep.phase("e", time.perf_counter() - t0)


def four_chips(rep, args, train_c, held, ref_values):
    """The SPMD plane on a (data=2, model=2) mesh: servers on the model
    axis hold cyclic row shards of nwk (paper section 2.2).  ``fit()``
    runs the one compiled sweep that ``Session.make_step()`` hands out,
    its state placed with the sweep's shardings: one compile, in the
    first sweep."""
    import jax

    from repro import api
    t0 = time.perf_counter()
    job = base_job(args, train_c, backend=api.SPMD, mesh_model=2,
                   use_kernels=True)
    print(f"[spmd] mesh data=2 x model=2, Pallas path, snapshot executor, "
          f"block_tokens {job.block_tokens}, route {job.route}", flush=True)
    model, res, values, compiles = train(rep, "spmd", job, held)
    rep.check("spmd", "one compile, in the first sweep",
              compiles[0] >= 1 and not any(compiles[1:]),
              f"programs compiled per sweep {compiles}")
    layout = res.nwk.layout
    shards = sorted((s.device.id, tuple(s.data.shape))
                    for s in res.nwk.value.addressable_shards)
    for dev_id, shape in shards:
        print(f"[spmd] device {dev_id}: nwk shard {shape}", flush=True)
    want = (layout.pad_rows // 2, NYTIMES["topics"])
    rep.check("spmd", "each device holds only its model shard of nwk",
              len(shards) == 4 and all(sh == want for _, sh in shards),
              f"shard {want} of {(layout.pad_rows, NYTIMES['topics'])}")
    for d in jax.devices():
        st = d.memory_stats() or {}
        print(f"[spmd] device {d.id} memory: in use "
              f"{st.get('bytes_in_use', 'n/a')} B, peak "
              f"{st.get('peak_bytes_in_use', 'n/a')} B", flush=True)
    gap = abs(values[-1] - ref_values[-1]) / ref_values[-1]
    rep.check("spmd", "held-out perplexity agrees with the one-chip run",
              gap < PPL_AGREE_SPMD,
              f"{values[-1]:.4f} vs {ref_values[-1]:.4f} (relative gap "
              f"{gap:.6f}, limit {PPL_AGREE_SPMD})")
    rep.phase("spmd", time.perf_counter() - t0)


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def cache_counter():
    """Counts persistent-cache hits and misses of this process."""
    import jax
    counts = {"hits": 0, "misses": 0}

    def listen(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def run(args, require_tpu: bool = True) -> dict:
    """All phases; raises SmokeFailed when a check fails."""
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = cache_counter()
    rep = Report()

    t0 = time.perf_counter()
    info = device_info()
    print(f"[a] device: platform {info['platform']}, kind {info['kind']}, "
          f"count {info['count']}", flush=True)
    want = 4 if args.four_chips else 1
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"no TPU found: JAX reports platform "
                         f"{info['platform']!r}; this smoke runs on a TPU")
    if require_tpu and info["count"] < want:
        raise SystemExit(f"needs {want} TPU chip(s), JAX sees "
                         f"{info['count']}")
    print(f"[a] compilation cache: {cache_dir}", flush=True)
    print(f"[a] widths: V={NYTIMES['vocab']} K={NYTIMES['topics']} "
          f"D={args.docs} mean length "
          f"{NYTIMES['mean_doc_len']}", flush=True)
    rep.phase("a", time.perf_counter() - t0)

    train_c, held = make_corpus(rep, args)
    model_c, z_c, values_c = phase_c(rep, args, train_c, held,
                                     use_kernels=args.four_chips)
    if args.four_chips:
        del model_c, z_c
        four_chips(rep, args, train_c, held, values_c)
    else:
        model_d = phase_d(rep, args, train_c, held, (model_c, z_c, values_c))
        del z_c
        phase_e(rep, model_c, model_d, held)

    files = sum(len(f) for _, _, f in os.walk(cache_dir))
    print(f"[f] compilation cache {cache_dir}: {files} files; this run "
          f"{cache['hits']} hits, {cache['misses']} misses", flush=True)
    import jax
    for d in jax.devices():
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use", "n/a")
        print(f"[f] device {d.id} peak memory {peak} B", flush=True)
    if rep.failed:
        raise SmokeFailed("failed checks: " + "; ".join(rep.failed))
    return {"ok": True, "device": info}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--docs", type=int, default=DOCS,
                    help=f"D, cut from {NYTIMES['docs']} (V and K are "
                         f"never cut)")
    ap.add_argument("--sweeps", type=int, default=3)
    ap.add_argument("--block-tokens", type=int, default=8192)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD plane on four chips and the "
                         "one-chip jnp run it is held to")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except SmokeFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
