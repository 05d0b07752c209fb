"""Paper Table 1 'Shuffle write' column, structurally: per-iteration
communication volume of the three architectures as a function of workers
and K.

  * lightlda-ps : parsed from the *compiled HLO* of the distributed sweep
    (the real collectives the SPMD program executes), per worker.
  * spark-em    : GraphX shuffle model, 2 K-float messages per token.
  * spark-online: lambda [K, V] broadcast per minibatch per worker.

This is the communication analysis that explains the paper's zero-shuffle
column; it runs the actual shard_map lowering on fake host devices.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from repro.core import lda_em as em

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ps_bytes_from_hlo(workers: int, model: int, vocab: int, k: int,
                      tokens: int) -> dict:
    """Compile the distributed sweep on fake devices in a subprocess and
    parse its collective bytes."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={workers}"
        import jax, jax.numpy as jnp, numpy as np, json
        from repro import ps
        from repro.core import lightlda as lda
        from repro.data import corpus as corpus_mod
        from repro.launch import lda as L
        from repro.analysis import hlo_stats as H
        from repro.sharding.mesh import make_mesh

        corp = corpus_mod.synthetic_corpus(300, {vocab}, true_topics=8,
            mean_doc_len={max(tokens // 300, 8)}, seed=0)
        cfg = lda.LDAConfig(num_topics={k}, vocab_size={vocab},
                            block_tokens=1024, num_shards={model})
        data = {workers} // {model}
        mesh = make_mesh((data, {model}), ("data", "model"))
        fn = L.make_spmd_sweep(mesh, cfg)
        shards = corpus_mod.shard_tokens(corp, {workers}, cfg.block_tokens)
        npad = max(s[0].shape[0] for s in shards)
        dmax = max(s[3].shape[0] for s in shards)
        def sds(shape, dt): return jax.ShapeDtypeStruct(shape, dt)
        W = {workers}
        lowered = jax.jit(fn).lower(
            sds((W, npad), jnp.int32), sds((W, npad), jnp.int32),
            sds((W, npad), jnp.int32), sds((W, npad), jnp.bool_),
            sds((W, dmax), jnp.int32), sds((W, dmax), jnp.int32),
            sds((W, dmax, cfg.K), jnp.int32),
            sds((ps.client_for(cfg).matrix(cfg.V, cfg.K).value.shape), jnp.int32),
            sds((cfg.K,), jnp.int32), sds((W, 2), jnp.uint32))
        st = H.analyze_text(lowered.compile().as_text())
        print(json.dumps(dict(wire=st.coll_wire_bytes,
                              counts={{k2: v for k2, v in st.coll_counts.items() if v}})))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(fast: bool = False):
    vocab, k, tokens = (800, 20, 30_000) if fast else (2000, 50, 100_000)
    for workers, model in ([(8, 2)] if fast else [(4, 2), (8, 2), (8, 4)]):
        ps = ps_bytes_from_hlo(workers, model, vocab, k, tokens)
        em_bytes = em.shuffle_bytes_per_iter(
            tokens, em.EMConfig(num_topics=k, vocab_size=vocab))
        online_bytes = k * vocab * 4 * workers
        print(f"comm,workers={workers},servers={model},K={k},"
              f"ps_wire_per_worker={ps['wire']/1e6:.2f}MB,"
              f"em_shuffle={em_bytes/1e6:.2f}MB,"
              f"online_broadcast={online_bytes/1e6:.2f}MB,"
              f"ps_collectives={ps['counts']}")
    return True


if __name__ == "__main__":
    main()
