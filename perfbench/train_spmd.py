"""Training cells on four chips: steady sweeps of the SPMD plane.

Set-up generates the configuration's corpus from the seed, builds the job
through the program's entry point (``repro.api.LDAJob(backend="spmd")``
-> ``Session.make_step()``, the compiled sweep ``APSLDA.fit()`` runs on
that plane) over a ``(data, model)`` mesh of the configuration's shape,
and runs one sweep, which compiles.  The window then runs whole sweeps of
that same step until ``seconds`` have passed, as ``train.py`` does;
tokens per second count every worker's tokens.

``correct`` compares what the window's sweeps produced with
``ref_lda_spmd``:

* first, the program's per-worker token and document arrays must equal
  the reference's partition of the corpus, or the run ends there;
* ``z_mismatch``: in one block per worker drawn from the seed in every
  window sweep, the tokens whose new topic differs from the reference's,
  resampled from the same inputs, ties left out;
* ``count_mismatch``: entries of the final n_wk (gathered from the
  servers' shards), n_k and every worker's n_dk that differ from
  histograms of the final assignments.

    python3 perfbench/train_spmd.py --workload <name> --seeds 1 2 3

reads, per seed, the numbers that set the limits (``control.py``'s
readings for this kind of cell): a sound run of two checked sweeps, the
bfloat16 reference in the program's place, and faults planted in the
program's output.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gen  # noqa: E402
import harness  # noqa: E402
import ref_lda_spmd  # noqa: E402
import tracing  # noqa: E402
from harness import log  # noqa: E402

# Limit of ``z_mismatch``: the one-chip cells' limit, confirmed at this
# cell's size by its own readings (PERF.md section 2).
Z_MISMATCH_LIMIT = 4


def build(cell, seed: int):
    """The corpus, the program's SPMD step, its placed state and info."""
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
        backend=api.SPMD, mesh_model=cfg["mesh"]["model"],
        staleness=cfg["staleness"],
        route=api.HybridRoute(hot_words=cfg["hot_words"], use_kernel=False))
    t = time.perf_counter()
    state, step, info = api.Session(job, log_fn=log).make_step()
    jax.block_until_ready(state)
    mesh = (info["mesh_data"], info["mesh_model"])
    if mesh != (cfg["mesh"]["data"], cfg["mesh"]["model"]):
        raise SystemExit(f"the program's mesh is {mesh}; the configuration "
                         f"asks for {cfg['mesh']}")
    log(f"[setup] state: {info['mode']} executor, mesh {mesh}, "
        f"{info['n_blocks']} blocks of {cfg['block_tokens']} per worker, "
        f"route {info['route']}, nwk_carry {info['nwk_carry']}, in "
        f"{time.perf_counter() - t:.3f} s")
    return corp, state, step, info


def sweep_keys(seed: int, n: int):
    import jax
    return [jax.random.PRNGKey(int(k))
            for k in gen.key_ints(seed, "keys", n + 1)[1:]]


def reference_partition(cell, corp: dict, state):
    """The reference's partition, after checking that the program's
    per-worker arrays hold it: every later comparison rests on it."""
    import numpy as np
    cfg = cell.config
    workers = cfg["mesh"]["data"] * cfg["mesh"]["model"]
    part = ref_lda_spmd.partition(corp["w"], corp["d"], corp["doc_len"],
                                  workers, cfg["block_tokens"])
    for name in ref_lda_spmd.Partition._fields:
        got, want = np.asarray(getattr(state, name)), getattr(part, name)
        if got.shape != want.shape or np.any(got != want):
            raise SystemExit(f"the program's per-worker {name} is not the "
                             f"reference's partition of the corpus")
    log(f"[check] partition: {workers} workers of {part.w.shape[1]} token "
        f"slots and {part.doc_len.shape[1]} documents, as the reference's")
    return part


def conservation(cell, part, state) -> int:
    """Entries of the program's counts that differ from histograms of its
    final assignments: n_wk read back from its servers' cyclic rows (row
    r is row r // S of server r % S), n_k, and each worker's n_dk."""
    import numpy as np
    cfg = cell.config
    k, v, s = cfg["topics"], cfg["vocab"], cfg["mesh"]["model"]
    z = np.asarray(state.z)
    nwk_ref, nk_ref = ref_lda_spmd.word_counts(part, z, v, k)
    phys = np.asarray(state.nwk)
    rows = -(-v // s)
    r = np.arange(v)
    bad = int(np.sum(phys[(r % s) * rows + r // s] != nwk_ref))
    p = np.arange(phys.shape[0])
    bad += int(np.sum(phys[(p % rows) * s + p // rows >= v] != 0))
    del nwk_ref
    bad += int(np.sum(np.asarray(state.nk) != nk_ref))
    ndk = np.asarray(state.ndk)
    for j in range(z.shape[0]):
        bad += int(np.sum(ndk[j] != ref_lda_spmd.doc_counts(part, z, j, k)))
    return bad


def check(cell, seed: int, part, sweeps: list, control: bool = False
          ) -> dict:
    """Reference replay of one block per worker, drawn from the seed, of
    every (z_in, z_out, key) sweep."""
    import numpy as np
    cfg = cell.config
    workers, ntok = part.w.shape
    n_blocks = ntok // cfg["block_tokens"]
    g = gen.rng(seed, "sample")
    out = {"checked": 0, "ties": 0, "mismatch": 0, "control": 0}
    for z_in, z_out, key in sweeps:
        picks = g.integers(0, n_blocks, size=workers)
        got = ref_lda_spmd.replay(
            part, np.asarray(z_in), np.asarray(z_out), key, picks,
            block=cfg["block_tokens"], num_topics=cfg["topics"],
            vocab_size=cfg["vocab"], mh_steps=cfg["mh_steps"],
            alpha=cfg["alpha"], beta=cfg["beta"], control=control)
        for name in out:
            out[name] += got[name]
    log(f"[check] reference: {out['checked']} tokens compared, "
        f"{out['ties']} ties left out, {out['mismatch']} differ")
    return out


def run(cell, *, seed: int, seconds: int, trace: bool, t0: float,
        peaks) -> dict:
    import jax
    tr = cell.traffic
    corp, state, step, info = build(cell, seed)
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
    ntok = len(corp["w"])
    log(f"[window] {len(sweeps)} sweeps of {ntok} tokens in {window_s:.6f} s;"
        f" {compiles.count} compiles {compiles.names}")

    part = reference_partition(cell, corp, state)
    counts_bad = conservation(cell, part, state)
    del state, step
    ref = check(cell, seed, part, sweeps)
    nbytes = info["exchange_bytes_per_sweep"]
    log(f"[window] exchange bytes per device per sweep: {nbytes}")

    out = {"end_to_end": {"setup_s": setup_s,
                          "train_tokens_per_s": ntok * len(sweeps)
                          / window_s},
           "memory_peak_bytes": peak, "attempted": len(sweeps),
           "failed": 0,
           "checks": {"z_mismatch": {"value": ref["mismatch"],
                                     "limit": Z_MISMATCH_LIMIT},
                      "count_mismatch": {"value": counts_bad, "limit": 0}}}
    if trace:
        summary = tracing.TraceSummary.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["run"] = harness.Run(
            summary,
            counters={"compiles_in_window": compiles.count,
                      "sweeps": len(sweeps),
                      "exchange_bytes_per_sweep": nbytes},
            work={}, peaks=peaks)
    return out


def readings(cell, seed: int) -> dict:
    """One warm-up sweep and two checked ones; the checks read on the
    program's output, on the bfloat16 reference in its place, and on
    the output with a fault planted in it: the state returned unchanged,
    half of the tokens left unsampled, one token per block altered."""
    import numpy as np
    cfg = cell.config
    corp, state, step, _ = build(cell, seed)
    keys = sweep_keys(seed, 3)
    zs = []
    for key in keys:
        state = step(state, key)
        zs.append(np.asarray(state.z))
    part = reference_partition(cell, corp, state)
    out = {"seed": seed, "count_mismatch": conservation(cell, part, state)}
    del state, step
    sweeps = [(zs[0], zs[1], keys[1]), (zs[1], zs[2], keys[2])]
    ref = check(cell, seed, part, sweeps, control=True)
    out.update(checked=ref["checked"], ties=ref["ties"],
               sound=ref["mismatch"], control=ref["control"])
    pos = np.arange(part.w.shape[1])[None, :]
    block, k = cfg["block_tokens"], cfg["topics"]
    planted = {
        "unchanged": lambda zi, zo: zi,
        "half": lambda zi, zo: np.where(pos % 2 == 1, zi, zo),
        "token": lambda zi, zo: np.where(pos % block == 0, (zo + 1) % k, zo),
    }
    for name, fault in planted.items():
        out[name] = check(cell, seed, part,
                          [(zi, fault(zi, zo), key)
                           for zi, zo, key in sweeps])["mismatch"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="limit readings of an SPMD "
                                             "training cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_spec(), args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.device_info(cell.chips, True)
    harness.enable_compile_cache()
    for seed in args.seeds:
        t = time.perf_counter()
        row = readings(cell, seed)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
