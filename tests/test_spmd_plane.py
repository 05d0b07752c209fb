"""The SPMD plane through ``Session.make_step()`` on a (data=2, model=2)
mesh of forced host devices, against the benchmark's plain reference
(``perfbench/ref_lda_spmd.py``), which imports nothing of the program.

jax pins the device count at first init, so the plane runs once, in a
subprocess with four host devices; the tests read what it reports.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import json, sys
sys.path[:0] = ["perfbench", "src"]
import jax, jax.numpy as jnp, numpy as np
import ref_lda_spmd
from repro import api
from repro.data import corpus as corpus_mod

V, K, BLOCK = 300, 8, 512
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, d, **kw: compiles.append(kw.get("fun_name"))
    if event == "/jax/core/compile/backend_compile_duration" else None)
corp = corpus_mod.generate_lda_corpus(seed=0, num_docs=120, mean_doc_len=40,
                                      vocab_size=V, num_topics=6)
job = api.LDAJob(corpus=corp, num_topics=K, vocab_size=V, block_tokens=BLOCK,
                 backend=api.SPMD, mesh_model=2, sweeps=1, eval_every=0,
                 seed=3, route=api.HybridRoute(hot_words=40,
                                               use_kernel=False))
state, step, info = api.Session(job, log_fn=lambda *a: None).make_step()
part = ref_lda_spmd.partition(corp.w, corp.d, corp.doc_len, 4, BLOCK)
out = {"info": {k: info[k] for k in ("mode", "mesh_data", "mesh_model",
                                      "nwk_carry",
                                      "exchange_bytes_per_sweep")},
       "partition": int(sum(np.sum(np.asarray(getattr(state, f))
                                   != getattr(part, f))
                            for f in part._fields)),
       "shardings": [str(x.sharding.spec) for x in state],
       "compiles": []}
sweeps = []
for i in range(3):
    key = jax.random.PRNGKey(100 + i)
    z_in = np.asarray(state.z)
    n = len(compiles)
    state = step(state, key)
    jax.block_until_ready(state.z)
    out["compiles"].append(compiles[n:])
    sweeps.append((z_in, np.asarray(state.z), key))


def counts_bad(z):
    nwk, nk = ref_lda_spmd.word_counts(part, z, V, K)
    rows = -(-V // 2)
    r = np.arange(V)
    bad = int(np.sum(np.asarray(state.nwk)[(r % 2) * rows + r // 2] != nwk))
    bad += int(np.sum(np.asarray(state.nk) != nk))
    ndk = np.asarray(state.ndk)
    return bad + sum(int(np.sum(ndk[j] != ref_lda_spmd.doc_counts(
        part, z, j, K))) for j in range(4))


def replay(z_in, z_out, key):
    got = {"checked": 0, "ties": 0, "mismatch": 0}
    for b in range(part.w.shape[1] // BLOCK):
        r = ref_lda_spmd.replay(part, z_in, z_out, key, [b] * 4,
                                block=BLOCK, num_topics=K, vocab_size=V,
                                mh_steps=2, alpha=0.1, beta=0.01)
        for k in got:
            got[k] += r[k]
    return got


z_last = sweeps[-1][1]
out["valid"] = int(part.valid.sum())
out["counts"] = counts_bad(z_last)
out["sweeps"] = [replay(*s) for s in sweeps[:2]]
# a planted fault: one token of the last sweep's output altered
j, p = 1, int(np.nonzero(part.valid[1])[0][7])
bad = z_last.copy()
bad[j, p] = (bad[j, p] + 1) % K
out["fault_counts"] = counts_bad(bad)
out["fault_replay"] = replay(sweeps[-1][0], bad, sweeps[-1][2])
print("RESULT " + json.dumps(out))
"""


def _env():
    return dict(os.environ,
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))


@pytest.fixture(scope="module")
def plane():
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                         capture_output=True, text=True, timeout=600,
                         env=_env(), cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_state_is_the_reference_partition_placed_with_the_sweep_specs(plane):
    assert plane["partition"] == 0
    assert plane["shardings"] == (
        ["PartitionSpec(('data', 'model'), None)"] * 6
        + ["PartitionSpec(('data', 'model'),)", "PartitionSpec('model',)",
           "PartitionSpec()"])
    info = plane["info"]
    assert (info["mode"], info["mesh_data"], info["mesh_model"],
            info["nwk_carry"]) == ("spmd", 2, 2, "flat")
    assert set(info["exchange_bytes_per_sweep"]) == {
        "pull_all_gather", "hot_psum", "cold_all_gather", "nk_psum"}
    assert all(v > 0 for v in info["exchange_bytes_per_sweep"].values())


@pytest.mark.parametrize("sweep", [0, 1])
def test_every_block_equals_the_reference_replay(plane, sweep):
    got = plane["sweeps"][sweep]
    assert got["mismatch"] == 0
    assert got["checked"] + got["ties"] == plane["valid"]
    assert got["ties"] <= 0.01 * plane["valid"]


def test_counts_equal_the_histograms_of_z(plane):
    assert plane["counts"] == 0


def test_three_sweeps_compile_once(plane):
    assert [len(c) for c in plane["compiles"]] == [1, 0, 0], \
        plane["compiles"]
    assert "spmd_sweep" in plane["compiles"][0][0]


def test_a_planted_one_token_fault_is_caught(plane):
    assert plane["fault_counts"] >= 2          # the token's two topics
    assert plane["fault_replay"]["mismatch"] >= 1
