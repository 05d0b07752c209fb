"""Compile rehearsals: the Pallas kernels of the main path, compiled by the
TPU compiler for a described (not attached) v5e chip at real widths.

Interpret mode on the CPU checks what a kernel computes; only the chip's
compiler refuses a block shape off the (8, 128) tiling or a kernel that
overflows scoped VMEM.  Nothing here runs: a pass says the kernel compiles
for the chip, not how fast it is.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
every test file.  All rehearsals stay in this one file for the same reason.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import alias_build, delta_push, mh_sample

V_NYT = 102_660          # NYTimes vocabulary (the serving alias build's rows)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


def _mh_sample(sharding, k, frozen):
    s = _spec(sharding)
    b = 2048
    f32, i32 = jnp.float32, jnp.int32

    def fn(*a):
        return mh_sample.mh_sample_call(
            *a, num_topics=k, vocab_size=V_NYT, alpha=0.1, beta=0.01,
            mh_steps=2, interpret=False, frozen=frozen)

    return _compile(fn, s((1, b), i32), s((b, k), f32), s((b, k), f32),
                    s((1, k), f32), s((b, k), f32), s((b, k), i32),
                    s((2, b), f32), s((2, b), f32), s((2, b), i32),
                    s((2, b), f32))


def _alias_build(sharding, k):
    s = _spec(sharding)
    v = 1024
    f32, i32 = jnp.float32, jnp.int32

    def fn(*a):
        return alias_build.alias_build_call(*a, num_cols=k, interpret=False)

    return _compile(fn, s((v, k), f32), s((v, k), i32), s((v, k), i32),
                    s((v, 1), i32), s((v, 1), i32))


def _delta_push(sharding):
    s = _spec(sharding)
    tok = s((1, 8192), jnp.int32)

    def fn(*a):
        return delta_push.delta_push_call(*a, vocab_pad=2048, k_pad=1024,
                                          interpret=False)

    return _compile(fn, tok, tok, tok, tok)


def _delta_apply_coo(sharding):
    s = _spec(sharding)
    tok = s((1, 16384), jnp.int32)

    def fn(*a):
        return delta_push.delta_apply_coo_call(*a, vocab_pad=2048,
                                               k_pad=1024, interpret=False)

    return _compile(fn, tok, tok, tok)


@pytest.mark.parametrize("frozen", [False, True])
@pytest.mark.parametrize("k", [128, 1024])
def test_mh_sample_compiles(one_chip, k, frozen):
    _mh_sample(one_chip, k, frozen)


@pytest.mark.parametrize("k", [128, 1024])
def test_alias_build_compiles(one_chip, k):
    _alias_build(one_chip, k)


def test_delta_push_compiles(one_chip):
    _delta_push(one_chip)


def test_delta_apply_coo_compiles(one_chip):
    _delta_apply_coo(one_chip)


# ---------------------------------------------------------------------------
# Names a profiler trace reads: kernel names and the sweep's phase scopes
# ---------------------------------------------------------------------------

def _custom_calls(text):
    """The tpu_custom_call instruction lines, as a trace names them
    (``%name = shape custom-call(...)``)."""
    return [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
            if "custom-call(" in ln and "tpu_custom_call" in ln]


@pytest.mark.parametrize("name,build", [
    ("mh_sample", lambda c: _mh_sample(c, 128, False)),
    ("mh_sample_frozen", lambda c: _mh_sample(c, 128, True)),
    ("alias_build", lambda c: _alias_build(c, 128)),
    ("delta_push", _delta_push),
    ("delta_apply_coo", _delta_apply_coo),
])
def test_kernel_instruction_carries_its_name(one_chip, name, build):
    calls = _custom_calls(build(one_chip).as_text())
    assert len(calls) == 1, calls
    assert re.match(rf"^%{name}(\.\d+)? = ", calls[0]), calls[0][:120]


SWEEP_K = 1024


@pytest.fixture(scope="module")
def sweeps(one_chip):
    """Small snapshot and pipelined sweeps at K=1,024 (4 blocks), Pallas
    path and hybrid push as the training cells run them, compiled for the
    described chip: ``{kind: (jitted step, abstract args, HLO text)}``."""
    import numpy as np

    from repro import ps
    from repro.core import lightlda as lda
    from repro.data import corpus as corpus_mod
    from repro.train import async_exec

    corp = corpus_mod.generate_lda_corpus(
        seed=0, num_docs=200, mean_doc_len=40, vocab_size=4096,
        num_topics=8)
    cfg = lda.LDAConfig(num_topics=SWEEP_K, vocab_size=4096,
                        block_tokens=2048, use_kernels=True,
                        kernel_interpret=False)
    n = int(corp.w.shape[0])
    st = jax.eval_shape(
        lambda w, d: lda.init_state(jax.random.PRNGKey(0), w, d,
                                    corp.num_docs, cfg),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32))
    spec = _spec(one_chip)
    state = jax.tree.map(lambda x: spec(x.shape, x.dtype), st)
    key = spec((2,), jnp.uint32)
    route = ps.HybridRoute(hot_words=512, use_kernel=False)

    rpb = st.nwk.layout.pad_rows // 4
    w = np.concatenate([corp.w, np.zeros(st.w.shape[0] - n, np.int32)])
    valid = np.arange(st.w.shape[0]) < n
    idx, bval = lda.block_token_index(w, valid, rpb, st.nwk.layout)
    out = {}
    for kind, fn, args in (
            ("snapshot", async_exec._jit_as(
                "snapshot_sweep", lambda s, k: async_exec.snapshot_sweep(
                    s, k, cfg, route=route)), (state, key)),
            ("pipelined", async_exec._jit_as(
                "pipelined_sweep", lambda s, k, i, b:
                async_exec.pipelined_sweep(s, k, cfg, i, b, rpb,
                                           route=route)),
             (state, key, spec(idx.shape, jnp.int32),
              spec(bval.shape, jnp.bool_)))):
        out[kind] = (fn, args, fn.lower(*args).compile().as_text())
    return out


@pytest.mark.parametrize("metric", ["mh_sample_roofline.train",
                                    "alias_build_roofline.train"])
def test_kernel_regexes_of_the_benchmark_find_the_named_kernels(sweeps,
                                                                 metric):
    path = os.path.join(REPO, "perfbench", "metrics", metric + ".py")
    ns = {}
    exec(open(path).read(), ns)
    hit = [ln for ln in _custom_calls(sweeps["snapshot"][2])
           if re.search(ns["KERNEL"], ln)]
    assert len(hit) == 1, _custom_calls(sweeps["snapshot"][2])
    assert hit[0].startswith("%" + metric.split("_roofline")[0])


# instructions that compute nothing themselves: values passed around,
# control flow, and the start/done markers of the asynchronous copies and
# slices that XLA's memory-space assignment adds
FREE = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
        "while", "conditional", "call", "copy-start", "copy-done",
        "slice-start", "slice-done")


@pytest.mark.parametrize("kind", ["snapshot", "pipelined"])
def test_scope_table_finds_every_phase_of_the_sweep(sweeps, kind,
                                                    monkeypatch):
    from repro.obs import scopes
    fn, args, text = sweeps[kind]
    monkeypatch.setattr(scopes, "_PROGRAMS", [])
    scopes.register(fn.__name__, fn, args)
    table = scopes.scope_table()
    assert set(scopes.PHASES) <= set(table.values())
    assert scopes.AMBIGUOUS not in table.values()
    # every operation of the entry computation and the loop body, the
    # scan's own counter and the pass-through output copies included
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text)) | {entry}
    ops = [i for i in scopes.instructions(text)
           if i.computation in bodies and i.opcode not in FREE]
    unscoped = [i.name for i in ops if i.phase is None]
    assert len(ops) > 50
    assert len(unscoped) <= 0.05 * len(ops), unscoped
    assert "jit_" + fn.__name__ in text


# ---------------------------------------------------------------------------
# The n_wk aggregate stays out of relayouts inside the sweep's loop
# ---------------------------------------------------------------------------

K_NYT = 1024
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$")


def _loop_relayouts(text, v, k):
    """Reshapes and copies in a loop body whose result is a whole
    ``[v, k]`` int32 table, in the tiled or the flat layout."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    whole = re.compile(rf" = s32\[(?:{v},{k}|{v * k})\]\S* (?:reshape|copy)\(")
    computation, found = None, []
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None:
            computation = c.group(1)
        elif computation in bodies and whole.search(line):
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("route", ["hybrid", "dense"])
def test_sweep_loop_has_no_nwk_relayout(one_chip, route):
    """``snapshot_sweep`` at NYTimes widths, Pallas path: the training
    cells' hybrid push (its cold tail scattered by XLA) and the dense
    push.  Neither relays the whole n_wk table out in the loop."""
    from repro import ps
    from repro.core import lightlda as lda
    from repro.train import async_exec

    cfg = lda.LDAConfig(num_topics=K_NYT, vocab_size=V_NYT,
                        block_tokens=8192, use_kernels=True,
                        kernel_interpret=False)
    n, num_docs = 20_000, 300
    st = jax.eval_shape(
        lambda w, d: lda.init_state(jax.random.PRNGKey(0), w, d, num_docs,
                                    cfg),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32))
    spec = _spec(one_chip)
    state = jax.tree.map(lambda x: spec(x.shape, x.dtype), st)
    rt = {"hybrid": ps.HybridRoute(hot_words=2000, use_kernel=False),
          "dense": ps.DenseRoute()}[route]
    text = jax.jit(lambda s, k: async_exec.snapshot_sweep(
        s, k, cfg, route=rt)).lower(state, spec((2,), jnp.uint32)) \
        .compile().as_text()
    assert "body=" in text
    assert _loop_relayouts(text, V_NYT, K_NYT) == []


# ---------------------------------------------------------------------------
# The SPMD sweep on a described four-chip host
# ---------------------------------------------------------------------------

def test_spmd_sweep_compiles_with_its_collectives_under_exchange(one_chip):
    """The SPMD plane's step (``make_spmd_step``) on a (data=2, model=2)
    mesh of a described v5e:2x2, Pallas path and hybrid push as the
    four-chip cell runs it: it compiles for the chip, and every
    collective the compiler put in lies under ``exchange``, inside the
    pull or the push."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    from repro import ps
    from repro.api import session
    from repro.core import lightlda as lda
    from repro.obs import scopes
    from repro.sharding.mesh import make_mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    v, npad, dmax = 4096, 4 * 2048, 64
    cfg = lda.LDAConfig(num_topics=SWEEP_K, vocab_size=v, block_tokens=2048,
                        num_shards=2, use_kernels=True,
                        kernel_interpret=False)
    shapes = session.SpmdState(
        *[(4, npad)] * 4, (4, dmax), (4, dmax), (4, dmax, SWEEP_K),
        (v, SWEEP_K), (SWEEP_K,))
    types = session.SpmdState(*[jnp.int32] * 3, jnp.bool_, *[jnp.int32] * 5)
    state = session.SpmdState(*[
        jax.ShapeDtypeStruct(s, t, sharding=NamedSharding(mesh, p))
        for s, t, p in zip(shapes, types, session.spmd_specs())])
    step = session.make_spmd_step(
        mesh, cfg, 4, route=ps.HybridRoute(hot_words=512, use_kernel=False))
    text = step.lower(state, jax.ShapeDtypeStruct((2,), jnp.uint32)) \
        .compile().as_text()
    assert "tpu_custom_call" in text
    coll = [i for i in scopes.instructions(text)
            if i.opcode.split("-start")[0] in ("all-gather", "all-reduce",
                                               "reduce-scatter")]
    assert {i.opcode.split("-start")[0] for i in coll} >= {"all-gather",
                                                           "all-reduce"}
    assert all(i.exchange and i.phase in ("ps.pull", "ps.push")
               for i in coll), [(i.name, i.phase, i.exchange) for i in coll]
