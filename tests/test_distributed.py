"""Multi-device SPMD tests.

jax pins the device count at first init, so these run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests in this process
keep seeing 1 device, per the dry-run contract)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _subprocess_env():
    return dict(os.environ,
                XLA_FLAGS="--xla_force_host_platform_device_count=8",
                PYTHONPATH=os.path.join(ROOT, "src"))


def _forced_device_count() -> int:
    """jax.device_count() as the subprocesses will see it.

    These tests construct >=2-device meshes; on hosts where forcing extra
    host-platform devices does not take (pinned accelerator backends,
    restricted runtimes) they must *skip*, not fail.  Probed in a
    subprocess because jax pins the device count at first init.
    """
    try:
        out = subprocess.run(
            [sys.executable, "-c", "import jax; print(jax.device_count())"],
            capture_output=True, text=True, timeout=120,
            env=_subprocess_env(), cwd=ROOT)
        return int(out.stdout.strip()) if out.returncode == 0 else 1
    except Exception:
        return 1


pytestmark = pytest.mark.skipif(
    _forced_device_count() < 2,
    reason="multi-device SPMD tests need >= 2 (forced host) devices")


def run_py(code: str, timeout=900) -> str:
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=_subprocess_env(), cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_lda_distributed_converges():
    """Paper's core loop on a (data=4, model=2) mesh: workers sample,
    servers hold cyclic n_wk rows, perplexity decreases."""
    out = run_py("""
        import subprocess, sys, json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import lightlda as lda, perplexity as ppl
        from repro.data import corpus as corpus_mod
        from repro.launch import lda as launch_lda

        corp = corpus_mod.generate_lda_corpus(seed=0, num_docs=200,
            mean_doc_len=40, vocab_size=300, num_topics=8)
        cfg = lda.LDAConfig(num_topics=10, vocab_size=300, block_tokens=512,
                            num_shards=2)
        hist = launch_lda.run_distributed(corp, cfg, sweeps=15, seed=0,
                                          eval_every=5, mesh_model=2)
        print("FIRST", hist[0]["perplexity"], "LAST", hist[-1]["perplexity"])
        assert hist[-1]["perplexity"] < hist[0]["perplexity"] * 0.99
    """)
    assert "LAST" in out


def test_moe_spmd_matches_dense():
    """Expert-parallel all-to-all path == dense oracle when capacity is
    ample (no drops)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import ModelConfig
        from repro.models import moe
        from repro.sharding.mesh import make_mesh
        from repro.sharding.specs import MeshCtx

        cfg = ModelConfig(name="t", arch_type="moe", num_layers=1,
            d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
            vocab_size=128, num_experts=4, top_k=2, moe_d_ff=32,
            num_shared_experts=1, capacity_factor=8.0, dtype="float32")
        key = jax.random.PRNGKey(0)
        params = moe.init_moe(key, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))

        y_ref, aux_ref = moe.moe_block(params, x, cfg, None)

        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = MeshCtx(mesh, ("data",), "model")
        # storage-shard the experts like specs.py would
        y_spmd, aux_spmd = jax.jit(
            lambda p, x: moe.moe_block(p, x, cfg, ctx))(params, x)
        err = float(jnp.abs(y_ref - y_spmd).max())
        rel = err / float(jnp.abs(y_ref).max())
        print("rel", rel)
        assert rel < 2e-5, rel
        # aux: the SPMD path averages per-shard load-balance losses, the
        # dense path computes the global one -- equal in expectation, not
        # per-batch; both are ~1.0-scale valid estimators
        assert abs(float(aux_ref) - float(aux_spmd)) < 0.25
    """)


def test_lm_train_step_on_mesh():
    """One sharded train step on a (4, 2) mesh runs and returns finite
    loss with params sharded per the spec table."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import registry
        from repro.configs.base import TrainConfig
        from repro.sharding.mesh import make_mesh
        from repro.sharding.specs import MeshCtx
        from repro.train import loop as train_loop

        cfg = registry.smoke_variant("gemma3-4b")
        mesh = make_mesh((4, 2), ("data", "model"))
        ctx = MeshCtx(mesh, ("data",), "model")
        state = train_loop.init_state(jax.random.PRNGKey(0), cfg, ctx)
        tc = TrainConfig(total_steps=5, warmup_steps=1, microbatch=2)
        step = train_loop.jit_train_step(cfg, tc, ctx, state, donate=False)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                  cfg.vocab_size, dtype=jnp.int32)
        mask = jnp.ones((8, 64), jnp.float32)
        state2, metrics = step(state, toks, toks, mask)
        assert bool(jnp.isfinite(metrics["loss"])), metrics
        print("loss", float(metrics["loss"]))
    """)


def test_pserver_spmd_pull_push():
    """spmd snapshot-pull/reduce-push primitives under shard_map."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import ps
        from repro.core.pserver import spmd_pull_all, spmd_push_reduce
        from repro.sharding.mesh import make_mesh

        mesh = make_mesh((8,), ("model",))
        dense = jnp.arange(64, dtype=jnp.int32).reshape(16, 4)
        client = ps.PSClient.create(num_shards=8)
        m = client.matrix_from_dense(dense)

        def body(local):
            full = spmd_pull_all(local, "model")
            delta = jnp.ones_like(full)
            mine = spmd_push_reduce(delta, "model", None, 8)
            return full, local + mine

        f = jax.shard_map(body, mesh=mesh, in_specs=P("model", None),
                      out_specs=(P(None, None), P("model", None)),
                      check_vma=False)
        full, updated = jax.jit(f)(m.value)
        # snapshot equals the full physical matrix
        np.testing.assert_array_equal(np.asarray(full), np.asarray(m.value))
        # each worker contributed 1 -> +8 per entry on the owner shard
        up = client.wrap_matrix(updated, 16).to_dense()
        np.testing.assert_array_equal(np.asarray(up), np.asarray(dense) + 8)
        print("ok")
    """)
