"""Training cells: steady sweeps of the in-memory snapshot executor.

Set-up generates the configuration's corpus from the seed, builds the job
through the program's entry point (``repro.api.LDAJob`` ->
``Session.make_step()``, the compiled sweep ``APSLDA.fit()`` runs) and
runs one sweep, which compiles.  The window then runs whole sweeps of
that same step until ``seconds`` have passed: from the first sweep's
dispatch to the last one's completion.

``correct`` compares what the window's sweeps produced:

* ``z_mismatch``: in blocks drawn from the seed in every window sweep,
  the tokens whose new topic differs from the plain reference's, resampled
  from the same inputs (``ref_lda.block_resample``), ties left out;
* ``count_mismatch``: entries of the final n_wk, n_k and n_dk that differ
  from histograms of the final assignments.
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

# Limit of ``z_mismatch``, set from chip readings at the cells' own sizes:
# sound runs read 0 on all seeds but two PubMed ones, which read 1 token of
# about 65,000 (a rounding difference no tie rule has named yet); the
# bfloat16 control reads 14 to 34, a state left unchanged about 30,000 and
# half the batch unsampled about 15,000.  One token altered per block reads
# 4 to 7 in a single sweep, and more over a window's two or three sweeps.
Z_MISMATCH_LIMIT = 4


def _padded(corp: dict, block: int):
    import numpy as np
    n = corp["w"].shape[0]
    pad = (-n) % block
    z = np.zeros(pad, np.int32)
    return (np.concatenate([corp["w"], z]), np.concatenate([corp["d"], z]),
            np.concatenate([np.ones(n, bool), np.zeros(pad, bool)]))


def build(cell, seed: int):
    """The program's compiled sweep and its initial state, from the seed."""
    import jax
    from repro import api
    from repro.data.corpus import Corpus
    cfg = cell.config
    t = time.perf_counter()
    corp = gen.config_corpus(cfg, seed, cfg["docs"])
    log(f"[setup] corpus: D={cfg['docs']} V={cfg['vocab']} "
        f"N={corp['w'].shape[0]} tokens in {time.perf_counter() - t:.3f} s")
    job = api.LDAJob(
        corpus=Corpus(corp["w"], corp["d"], corp["doc_start"],
                      corp["doc_len"], cfg["vocab"], corp["word_freq"]),
        num_topics=cfg["topics"], vocab_size=cfg["vocab"],
        alpha=cfg["alpha"], beta=cfg["beta"], mh_steps=cfg["mh_steps"],
        block_tokens=cfg["block_tokens"], sweeps=1, eval_every=0,
        seed=int(gen.key_ints(seed, "keys", 1)[0]), use_kernels=True,
        route=api.HybridRoute(hot_words=cfg["hot_words"], use_kernel=False))
    t = time.perf_counter()
    state, step, info = api.Session(job, log_fn=log).make_step()
    jax.block_until_ready(state.z)
    log(f"[setup] state: {info['mode']} executor, {info['n_blocks']} blocks "
        f"of {cfg['block_tokens']}, route {info['route']}, in "
        f"{time.perf_counter() - t:.3f} s")
    return corp, state, step


def sweep_keys(seed: int, n: int):
    import jax
    return [jax.random.PRNGKey(int(k))
            for k in gen.key_ints(seed, "keys", n + 1)[1:]]


def check(cell, seed: int, corp: dict, sweeps: list, control: bool = False
          ) -> dict:
    """Reference comparison of sampled blocks of every (z_in, z_out, key)
    sweep; with ``control`` also the bfloat16 reference's reading."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg, tr = cell.config, cell.traffic
    block, k_, v_ = cfg["block_tokens"], cfg["topics"], cfg["vocab"]
    w, d, valid = _padded(corp, block)
    c = ref_lda.Corpus(jnp.asarray(w), jnp.asarray(d), jnp.asarray(valid),
                       jnp.asarray(corp["doc_start"]),
                       jnp.asarray(corp["doc_len"]))
    n_blocks = w.shape[0] // block
    num_docs = corp["doc_len"].shape[0]
    g = gen.rng(seed, "sample")
    kw = dict(block=block, n_blocks=n_blocks, num_docs=num_docs,
              num_topics=k_, vocab_size=v_, mh_steps=cfg["mh_steps"],
              alpha=cfg["alpha"], beta=cfg["beta"])
    out = {"checked": 0, "ties": 0, "mismatch": 0, "control": 0}
    for z_in, z_out, key in sweeps:
        blocks = np.sort(g.choice(n_blocks, tr["check_blocks_per_sweep"],
                                  replace=False))
        words = np.concatenate([w[b * block:(b + 1) * block] for b in blocks])
        nwk, nk, _ = ref_lda.sweep_counts(c.w, c.d, c.valid, z_in, num_docs,
                                          k_, v_)
        tab = ref_lda.build_tables(nwk, nk, words, cfg["beta"])
        del nwk
        log(f"[check] alias tables of {tab.prob.shape[0]} words: "
            f"{float(tab.unsure.mean()):.3e} of entries after a tied "
            f"Vose test")
        for b in blocks:
            z_ref, tie = ref_lda.block_resample(c, z_in, z_out, key,
                                                int(b), tab, **kw)
            sl = slice(b * block, (b + 1) * block)
            decided = np.asarray(valid[sl]) & ~np.asarray(tie)
            z_ref = np.asarray(z_ref)
            got = np.asarray(z_out[sl])
            out["checked"] += int(decided.sum())
            out["ties"] += int((np.asarray(valid[sl]) & np.asarray(tie)).sum())
            out["mismatch"] += int((decided & (got != z_ref)).sum())
            if control:
                z_c, _ = ref_lda.block_resample(c, z_in, z_out, key, int(b),
                                                tab, dtype=jnp.bfloat16, **kw)
                out["control"] += int((decided & (np.asarray(z_c) != z_ref))
                                      .sum())
    log(f"[check] reference: {out['checked']} tokens compared, "
        f"{out['ties']} ties left out, {out['mismatch']} differ")
    return out


def conservation(cell, corp: dict, state) -> int:
    """Entries of the program's counts that differ from histograms of its
    assignments."""
    import jax.numpy as jnp
    cfg = cell.config
    w, d, valid = _padded(corp, cfg["block_tokens"])
    nwk, nk, ndk = ref_lda.sweep_counts(
        jnp.asarray(w), jnp.asarray(d), jnp.asarray(valid), state.z,
        corp["doc_len"].shape[0], cfg["topics"], cfg["vocab"])
    bad = (jnp.sum(nwk != state.nwk.to_dense())
           + jnp.sum(nk != state.nk.pull_all().result())
           + jnp.sum(ndk != state.ndk))
    return int(bad)


def run(cell, *, seed: int, seconds: int, trace: bool, t0: float,
        peaks) -> dict:
    import jax
    import jax.numpy as jnp
    cfg, tr = cell.config, cell.traffic
    corp, state, step = build(cell, seed)
    keys = sweep_keys(seed, tr["max_sweeps"] + tr["warmup_sweeps"])
    for i in range(tr["warmup_sweeps"]):
        state = step(state, keys[i])
        jax.block_until_ready(state.z)
    setup_s = time.perf_counter() - t0
    log(f"[setup] {setup_s:.3f} s to the window")

    compiles = harness.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    sweeps = []
    compiles.counting = True
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        for i in range(tr["warmup_sweeps"], len(keys)):
            z_in = state.z
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state = step(state, keys[i])
            with jax.profiler.TraceAnnotation("bench.sync"):
                jax.block_until_ready(state.z)
            sweeps.append((z_in, state.z, keys[i]))
            if time.perf_counter() - t_start >= seconds:
                break
    t_end = time.perf_counter()
    compiles.counting = False
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t_start
    peak = harness.memory_peak_bytes(cell.chips)
    ntok = int(corp["w"].shape[0])
    log(f"[window] {len(sweeps)} sweeps of {ntok} tokens in {window_s:.6f} s;"
        f" {compiles.count} compiles {compiles.names}")

    mismatch_counts = conservation(cell, corp, state)
    valid = jnp.asarray(_padded(corp, cfg["block_tokens"])[2])
    changed = sum(int(jnp.sum((a != b) & valid)) for a, b, _ in sweeps)
    del state, step
    ref = check(cell, seed, corp, sweeps)

    out = {"end_to_end": {"setup_s": setup_s,
                          "train_tokens_per_s": ntok * len(sweeps)
                          / window_s},
           "memory_peak_bytes": peak, "attempted": len(sweeps),
           "failed": 0,
           "checks": {"z_mismatch": {"value": ref["mismatch"],
                                     "limit": Z_MISMATCH_LIMIT},
                      "count_mismatch": {"value": mismatch_counts,
                                         "limit": 0}}}
    if trace:
        summary = tracing.TraceSummary.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        n = len(sweeps)
        out["run"] = harness.Run(
            summary,
            counters={"compiles_in_window": compiles.count, "sweeps": n},
            work={"mh_sample": work.mh_sample(ntok * n, cfg["mh_steps"],
                                              changed=changed),
                  "alias_build": work.alias_build(cfg["vocab"] * n,
                                                  cfg["topics"]),
                  "sweep": work.sweep(ntok * n, changed, cfg["vocab"],
                                      cfg["topics"], cfg["mh_steps"])},
            peaks=peaks)
    return out
