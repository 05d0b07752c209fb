"""LDA launcher -- a thin argv -> ``LDAJob`` translator over ``repro.api``.

Every scenario is one declarative job (DESIGN.md section 10): the
launcher only parses flags, optionally ingests a synthetic corpus, builds
the job and runs it through ``api.Session``.

Single-process:
  PYTHONPATH=src python -m repro.launch.lda --docs 2000 --vocab 5000 -k 100

Distributed (SPMD over N devices): workers = all mesh shards (tokens
split over data x model), servers = the model axis (cyclic rows of n_wk,
paper section 2.2).  On a TPU host ``--devices N`` needs N chips; under
``JAX_PLATFORMS=cpu`` it forces N host devices (appended to XLA_FLAGS):
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.lda --devices 8 \
      --mesh-model 2 ...

Out-of-core: ``--stream-dir`` streams a sharded on-disk corpus through
the PS client (optionally combined with ``--devices``: groups of stream
shards feed the SPMD workers).

Multi-process (network PS, DESIGN.md section 15): ``--backend net``
spawns an elastic localhost worker pool against an embedded server, or
against an already-running ``python -m repro.launch.ps_server`` when
``--server host:port`` is given:
  PYTHONPATH=src python -m repro.launch.lda --backend net --workers 4 \
      --stream-dir experiments/stream ...
"""
import argparse
import os
import sys


def _early_devices():
    """``--devices N`` on the CPU backend: force N host devices before jax
    initialises (a TPU host has its chips already)."""
    if "--devices" in sys.argv and os.environ.get("JAX_PLATFORMS") == "cpu":
        n = sys.argv[sys.argv.index("--devices") + 1]
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={n}")))


_early_devices()

import json

import jax

from repro import api
from repro.compile_cache import enable_compile_cache
# SPMD wiring lives in the api session now; re-exported here because the
# SPMD test/benchmark suites import it from the launcher.
from repro.api.session import (init_distributed_state,  # noqa: F401
                               make_spmd_sweep)
from repro.data import corpus as corpus_mod
from repro.data import stream as stream_mod


def _corpus_from_args(args):
    return corpus_mod.synthetic_corpus(
        args.docs, args.vocab, true_topics=args.true_topics,
        mean_doc_len=args.mean_doc_len, seed=args.seed)


def job_from_args(args) -> "api.LDAJob":
    """Translate the parsed argv into the declarative job (the launcher's
    whole remaining role)."""
    common = dict(num_topics=args.topics, mh_steps=args.mh_steps,
                  block_tokens=args.block_tokens,
                  use_kernels=args.kernels,
                  staleness=args.staleness, hot_words=args.hot_words,
                  model_blocks=args.model_blocks, seed=args.seed,
                  eval_every=args.eval_every, sweeps=args.sweeps,
                  epochs=args.epochs)
    if args.trace_dir:
        common.update(obs=api.ObsConfig(enabled=True, out_dir=args.trace_dir))
    if args.devices:
        if args.model_blocks:
            print("[lda] note: --model-blocks is in-process only (the SPMD "
                  "backend uses the full-snapshot executor); ignoring")
        common.update(backend=api.SPMD, mesh_model=args.mesh_model,
                      model_blocks=0)
    elif args.backend == api.NET:
        common.update(backend=api.NET, workers=args.workers,
                      server=args.server or None,
                      net_assign=args.net_assign)
    elif args.server:
        ap_error = ("--server requires --backend net")
        raise api.JobValidationError(ap_error)

    if args.stream_dir:
        if not os.path.exists(os.path.join(args.stream_dir,
                                           stream_mod.MANIFEST)):
            corp = _corpus_from_args(args)
            meta = stream_mod.write_sharded(args.stream_dir, corp,
                                            args.stream_shard_tokens)
            print(f"[lda] sharded {meta.num_tokens} tokens into "
                  f"{meta.num_shards} shards at {args.stream_dir}")
        ckpt = api.CheckpointPolicy()
        if not args.devices and args.backend != api.NET:
            path = args.checkpoint or os.path.join(args.out,
                                                   "stream_ckpt.npz")
            ckpt = api.CheckpointPolicy(path=path,
                                        every=args.checkpoint_every,
                                        resume=args.resume)
        elif args.checkpoint or args.resume:
            print("[lda] note: checkpoint/resume is not supported on the "
                  "streamed SPMD/net paths; ignoring")
        return api.LDAJob(stream_dir=args.stream_dir, checkpoint=ckpt,
                          **common)

    corp = _corpus_from_args(args)
    print(f"[lda] corpus: {corp.num_tokens} tokens, {corp.num_docs} docs, "
          f"V={corp.vocab_size}")
    ckpt = api.CheckpointPolicy()
    if args.checkpoint and not args.devices:
        ckpt = api.CheckpointPolicy(path=args.checkpoint)
    return api.LDAJob(corpus=corp, checkpoint=ckpt, **common)


# ---------------------------------------------------------------------------
# Programmatic wrappers (kept for the SPMD test suites and back-compat;
# each is a one-job session now).
# ---------------------------------------------------------------------------

def run_single(corp, cfg: "object", sweeps: int, seed: int,
               eval_every: int, out, model_blocks: int = 0,
               staleness: int = 0, hot_words=None):
    """Single-process training through the unified session (the old
    ``run_single`` contract: returns ``(state, history)``)."""
    job = api.LDAJob(corpus=corp, num_topics=cfg.num_topics,
                     vocab_size=cfg.vocab_size, alpha=cfg.alpha,
                     beta=cfg.beta, mh_steps=cfg.mh_steps,
                     block_tokens=cfg.block_tokens,
                     num_shards=cfg.num_shards,
                     use_kernels=cfg.use_kernels,
                     kernel_interpret=cfg.kernel_interpret,
                     model_blocks=model_blocks, staleness=staleness,
                     hot_words=hot_words, sweeps=sweeps, seed=seed,
                     eval_every=eval_every)
    res = api.Session(job).run()
    return res.state, res.history


def run_distributed(corp, cfg, sweeps, seed, eval_every, mesh_model: int,
                    staleness: int = 0, hot_words=None):
    """SPMD training through the unified session (the old
    ``run_distributed`` contract: returns the history list; bitwise-
    identical loop, see ``api.session._SpmdPlane``)."""
    job = api.LDAJob(corpus=corp, num_topics=cfg.num_topics,
                     vocab_size=cfg.vocab_size, alpha=cfg.alpha,
                     beta=cfg.beta, mh_steps=cfg.mh_steps,
                     block_tokens=cfg.block_tokens,
                     use_kernels=cfg.use_kernels,
                     kernel_interpret=cfg.kernel_interpret,
                     backend=api.SPMD, mesh_model=mesh_model,
                     staleness=staleness, hot_words=hot_words,
                     sweeps=sweeps, seed=seed, eval_every=eval_every)
    return api.Session(job).run().history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=1000)
    ap.add_argument("--mean-doc-len", type=int, default=80)
    ap.add_argument("--vocab", type=int, default=2000)
    ap.add_argument("--true-topics", type=int, default=20)
    ap.add_argument("-k", "--topics", type=int, default=50)
    ap.add_argument("--sweeps", type=int, default=50)
    ap.add_argument("--mh-steps", type=int, default=2)
    ap.add_argument("--block-tokens", type=int, default=8192)
    ap.add_argument("--kernels", action="store_true",
                    help="use the Pallas kernel path (interpret on CPU)")
    ap.add_argument("--devices", type=int, default=0,
                    help="run distributed over N devices: N chips on a TPU "
                         "host, N forced host devices under "
                         "JAX_PLATFORMS=cpu")
    ap.add_argument("--mesh-model", type=int, default=2)
    ap.add_argument("--backend", default="",
                    choices=["", api.IN_PROCESS, api.SPMD, api.NET],
                    help="parameter-server backend (default: inferred; "
                         "'net' trains through worker subprocesses against "
                         "a network PS, DESIGN.md sec. 15)")
    ap.add_argument("--server", default="",
                    help="net backend: address (host:port) of a running "
                         "launch.ps_server process (default: embed one)")
    ap.add_argument("--workers", type=int, default=2,
                    help="net backend: size of the localhost worker pool")
    ap.add_argument("--net-assign", default="dynamic",
                    choices=["dynamic", "static", "static_steal"],
                    help="net backend: shard-to-worker assignment policy")
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--model-blocks", type=int, default=0,
                    help="blocked/pipelined sweep (paper sec 3.4): pull the "
                         "model in N blocks instead of a full snapshot")
    ap.add_argument("--staleness", type=int, default=0,
                    help="bounded-staleness executor: up to S block deltas "
                         "in flight while a block samples (0 = synchronous; "
                         "rounded down so S+1 divides the block count)")
    ap.add_argument("--hot-words", type=int, default=None,
                    help="hybrid delta push: the H hottest words aggregate "
                         "densely (MXU one-hot matmul), the cold tail is "
                         "pushed as (row, col, +/-1) coordinate deltas "
                         "(default: all words dense)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default="",
                    help="enable the telemetry plane (repro.obs): write a "
                         "Perfetto-loadable trace.json + metrics.jsonl "
                         "under this directory; inspect with "
                         "python -m repro.launch.obs_report <dir>")
    ap.add_argument("--out", default="experiments/lda")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--stream-dir", default="",
                    help="out-of-core training: shard the corpus into (or "
                         "reuse a manifest at) this directory and stream "
                         "it through the PS client shard by shard")
    ap.add_argument("--stream-shard-tokens", type=int, default=65536,
                    help="token capacity of each stream shard (must be a "
                         "multiple of --block-tokens for snapshot mode)")
    ap.add_argument("--epochs", type=int, default=3,
                    help="stream trainer: full passes over the shard "
                         "stream (per-epoch shard-order shuffle)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="stream trainer: checkpoint PS state + cursor "
                         "every N shard visits (0: only at the end)")
    ap.add_argument("--resume", action="store_true",
                    help="resume the stream trainer from --checkpoint "
                         "(bitwise-identical continuation)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.devices and jax.device_count() != args.devices:
        ap.error(f"--devices {args.devices}: this host has "
                 f"{jax.device_count()} {jax.default_backend()} device(s); "
                 f"on a TPU host pass its chip count, or run under "
                 f"JAX_PLATFORMS=cpu to force host devices")

    if args.stream_dir:
        print(f"[lda] stream mode: training {args.epochs} epochs "
              f"(--sweeps is the in-memory trainer's knob and is ignored)")
    try:
        job = job_from_args(args)
        session = api.Session(job)
        result = session.run()
    except api.JobValidationError as e:
        ap.error(str(e))
        return

    if args.trace_dir:
        print(f"[lda] trace written to {job.obs.trace_path} (load in "
              f"Perfetto); summarise with: python -m "
              f"repro.launch.obs_report {args.trace_dir}")
    if args.backend == api.NET:
        print(f"[lda] net training done: {result.info.get('workers')} "
              f"workers against {result.info.get('server')}")
    elif args.stream_dir and not args.devices:
        print(f"[lda] stream training done ({result.info['mode']} "
              f"executor); checkpoint at {job.checkpoint.path}")
    elif args.checkpoint and not args.devices and not args.stream_dir:
        print(f"[lda] checkpointed assignments to {args.checkpoint}")

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "history.json"), "w") as f:
        json.dump(result.history, f, indent=2)


if __name__ == "__main__":
    main()
