"""Telemetry plane (repro.obs) test suite.

The load-bearing invariant: observation never perturbs computation --
training with tracing enabled is **bitwise identical** to tracing
disabled, for both the in-memory and the streamed planes, and a disabled
run writes no files at all.  Around that: the tracer's Chrome-trace
output, the HDR histogram's percentile error bound, the under-jit no-op
guard, the instrumented subsystems (ps.push routes, engine serving,
stream loader), spans on the profiler's clock, the sweep's phase scopes
and the benchmark's readers of them, the obs_report renderer, and
the satellite regressions (LogCallback timestamps/flush, fit_lda
deprecation warnings).
"""
from __future__ import annotations

import io
import json
import os

import numpy as np
import pytest

from repro import obs
from repro.obs.trace import NULL_SPAN, Tracer


# ---------------------------------------------------------------------------
# metrics: counters / gauges / histograms
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_error():
    from repro.obs.metrics import Histogram

    rng = np.random.default_rng(0)
    values = rng.uniform(0.1, 500.0, size=5000)
    h = Histogram("lat")
    for v in values:
        h.record(float(v))
    assert h.count == 5000
    assert h.vmin == pytest.approx(values.min())
    assert h.vmax == pytest.approx(values.max())
    assert h.mean == pytest.approx(values.mean(), rel=1e-6)
    for q in (50, 90, 95, 99):
        exact = np.percentile(values, q)
        got = h.percentile(q)
        assert got == pytest.approx(exact, rel=0.05), (q, got, exact)


def test_histogram_edge_cases():
    from repro.obs.metrics import Histogram

    h = Histogram("empty")
    assert h.percentile(99) == 0.0 and h.mean == 0.0
    h.record(0.0)          # clamped to a tiny positive bucket, not an error
    h.record(-5.0)
    assert h.count == 2


def test_registry_jsonl_roundtrip(tmp_path):
    from repro.obs.metrics import MetricsRegistry, load_jsonl

    reg = MetricsRegistry()
    reg.counter("hits").inc(3)
    reg.gauge("depth").set(7)
    reg.histogram("lat").record(12.5)
    path = str(tmp_path / "m.jsonl")
    reg.save(path)
    rows = {r["name"]: r for r in load_jsonl(path)}
    assert rows["hits"]["kind"] == "counter" and rows["hits"]["value"] == 3
    assert rows["depth"]["value"] == 7
    assert rows["lat"]["count"] == 1 and rows["lat"]["p50"] > 0


# ---------------------------------------------------------------------------
# tracer: Chrome-trace JSON, thread metadata
# ---------------------------------------------------------------------------

def test_tracer_chrome_trace_output(tmp_path):
    import threading
    import time

    tr = Tracer()
    with tr.span("outer", cat="test", foo=1) as sp:
        time.sleep(0.005)
        sp.set(bar=2)
    tr.complete("measured", time.perf_counter_ns() - 2_000_000,
                time.perf_counter_ns(), cat="pull", rows=3)
    tr.instant("mark", cat="test")
    path = str(tmp_path / "t.json")
    tr.save(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert spans["outer"]["dur"] >= 4000            # us; slept 5ms
    assert spans["outer"]["args"] == {"foo": 1, "bar": 2}
    assert spans["measured"]["dur"] >= 2000
    assert spans["measured"]["args"] == {"rows": 3}
    assert spans["measured"]["tid"] == spans["outer"]["tid"]
    metas = [e for e in events if e.get("ph") == "M"]
    assert [e["args"]["name"] for e in metas] == [
        threading.current_thread().name]
    assert any(e.get("ph") == "i" and e["name"] == "mark" for e in events)


def test_no_session_means_null_span():
    assert obs.active() is None
    sp = obs.span("anything", cat="x")
    assert sp is NULL_SPAN
    assert sp.sync_on("value") == "value"
    assert sp.end() == 0.0
    assert obs.tracer_for(None) is None
    assert obs.metrics_for(None) is None


def test_span_is_noop_under_jit_trace():
    import jax
    import jax.numpy as jnp

    tr = Tracer()
    seen = []

    @jax.jit
    def f(x):
        seen.append(tr.span("inside_trace"))
        return x + 1

    f(jnp.arange(3))
    assert seen[0] is NULL_SPAN
    # outside the trace the same tracer records normally
    assert tr.span("outside") is not NULL_SPAN


def test_obsconfig_is_hashable_and_jit_static_safe():
    from repro.infer.foldin import FoldInConfig
    from repro.train.async_exec import ExecConfig

    cfg = obs.ObsConfig(enabled=True, out_dir="x")
    assert hash(cfg) != 0 or True                  # hashable at all
    assert {cfg: 1}[cfg] == 1
    hash(FoldInConfig(obs=cfg))
    hash(ExecConfig(obs=cfg))


def test_session_install_restore_nesting():
    outer = obs.ObsSession(obs.ObsConfig(enabled=True, trace=True,
                                         metrics=False)).install()
    try:
        assert obs.active() is outer
        inner = obs.ObsSession(obs.ObsConfig(enabled=True)).install()
        assert obs.active() is inner
        inner.close(save=False)
        assert obs.active() is outer
    finally:
        outer.close(save=False)
    assert obs.active() is None


# ---------------------------------------------------------------------------
# the zero-perturbation invariant + disabled-mode smoke
# ---------------------------------------------------------------------------

def _tiny_job(corp, tmp_dir=None, **kw):
    from repro import api

    obs_cfg = (api.ObsConfig(enabled=True, out_dir=str(tmp_dir))
               if tmp_dir is not None else api.ObsConfig())
    return api.LDAJob(corpus=corp, num_topics=8, num_shards=2,
                      block_tokens=512, sweeps=3, eval_every=0, seed=0,
                      obs=obs_cfg, **kw)


def test_disabled_mode_writes_nothing(tmp_path, tiny_corpus):
    import dataclasses
    from repro import api

    out = tmp_path / "should_stay_empty"
    job = dataclasses.replace(
        _tiny_job(tiny_corpus),
        obs=api.ObsConfig(enabled=False, out_dir=str(out)))
    api.APSLDA(job, log_fn=lambda *a, **k: None).fit()
    assert obs.active() is None
    assert not out.exists()


def test_memory_plane_bitwise_identical_traced_vs_untraced(tmp_path,
                                                           tiny_corpus):
    from repro import api

    off = api.APSLDA(_tiny_job(tiny_corpus),
                     log_fn=lambda *a, **k: None).fit()
    on = api.APSLDA(_tiny_job(tiny_corpus, tmp_dir=tmp_path / "obs"),
                    log_fn=lambda *a, **k: None).fit()
    np.testing.assert_array_equal(on.nwk, off.nwk)
    np.testing.assert_array_equal(on.nk, off.nk)
    # the traced run actually produced its artifacts
    trace = tmp_path / "obs" / "trace.json"
    metrics = tmp_path / "obs" / "metrics.jsonl"
    assert trace.exists() and metrics.exists()
    with open(trace) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert {"exec.sweep", "exec.dispatch", "session.step"} <= names
    assert obs.active() is None                    # session closed


def test_stream_plane_bitwise_identical_traced_vs_untraced(tmp_path,
                                                           stream_dir):
    from repro import api

    path, _, _ = stream_dir

    def fit(obs_cfg):
        job = api.LDAJob(stream_dir=path, num_topics=8, num_shards=2,
                         block_tokens=512, epochs=1, eval_every=0,
                         seed=0, obs=obs_cfg)
        return api.APSLDA(job, log_fn=lambda *a, **k: None).fit()

    off = fit(api.ObsConfig())
    on = fit(api.ObsConfig(enabled=True, out_dir=str(tmp_path / "sobs")))
    np.testing.assert_array_equal(on.nwk, off.nwk)
    np.testing.assert_array_equal(on.nk, off.nk)
    with open(tmp_path / "sobs" / "trace.json") as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert "exec.sweep" in names
    assert "stream.load" in names                  # loader instrumented


# ---------------------------------------------------------------------------
# instrumented subsystems
# ---------------------------------------------------------------------------

def test_push_routes_labels_and_traffic():
    from repro import ps

    assert ps.DenseRoute().label == "dense"
    assert ps.CooRoute().label == "coo"
    assert ps.HybridRoute(hot_words=4).label == "hybrid"
    batch, rows, k = 100, 50, 8
    dense = ps.DenseRoute().traffic(batch, rows, k)
    assert dense["dense_rows"] == rows and dense["coo_cap"] == 0
    coo = ps.CooRoute().traffic(batch, rows, k)
    # cold_coo emits 2 coordinate entries per reassignment (-1 old, +1 new)
    assert coo["coo_cap"] == 2 * batch
    assert coo["coo_bytes"] == 2 * batch * 3 * 4
    hyb = ps.HybridRoute(hot_words=16).traffic(batch, rows, k)
    assert 0 < hyb["dense_rows"] <= 16 and hyb["coo_cap"] == 2 * batch


def test_ps_push_records_span_and_histogram():
    import jax.numpy as jnp
    from repro import ps

    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.integers(0, 40, 64, dtype=np.int32))
    re = ps.Reassign(rows=w, words=w,
                     z_old=jnp.asarray(rng.integers(0, 8, 64,
                                                    dtype=np.int32)),
                     z_new=jnp.asarray(rng.integers(0, 8, 64,
                                                    dtype=np.int32)),
                     changed=jnp.asarray(rng.random(64) < 0.5))
    s = obs.ObsSession(obs.ObsConfig(enabled=True)).install()
    try:
        h = ps.PSClient.create(num_shards=2).matrix(40, 8)
        h.with_route(ps.HybridRoute(hot_words=8)).push(re)
        pushes = [e for e in s.tracer.events()
                  if e.get("ph") == "X" and e["name"] == "ps.push"]
        assert len(pushes) == 1
        args = pushes[0]["args"]
        assert args["route"] == "hybrid" and args["batch"] == 64
        assert args["coo_cap"] == 128
        hist = s.metrics.get("ps.push_ms.hybrid")
        assert hist is not None and hist.count == 1
        assert s.metrics.get("ps.push_count.hybrid").value == 1
    finally:
        s.close(save=False)


def test_engine_serving_metrics(tmp_path, tiny_corpus):
    from repro import api
    from repro.infer.engine import EngineConfig, QueryEngine
    from repro.infer.foldin import FoldInConfig

    model = api.APSLDA(_tiny_job(tiny_corpus),
                       log_fn=lambda *a, **k: None).fit()
    s = obs.ObsSession(obs.ObsConfig(enabled=True)).install()
    try:
        eng = QueryEngine(model.publisher(),
                          EngineConfig(max_batch=4,
                                       foldin=FoldInConfig(num_sweeps=2,
                                                           burnin=1)))
        rng = np.random.default_rng(0)
        docs = [rng.integers(0, 300, size=n).astype(np.int32)
                for n in (5, 9, 17, 30, 31, 12)]
        for d in docs:
            eng.submit(d)
        assert s.metrics.get("serve.queue_depth").value == len(docs)
        out = eng.flush()
        assert len(out) == len(docs)
        req = s.metrics.get("serve.request_ms")
        assert req.count == len(docs)
        assert req.summary()["p99"] >= req.summary()["p50"] > 0
        occ = s.metrics.get("serve.batch_occupancy")
        assert occ.count >= 2                       # several buckets/batches
        names = {e["name"] for e in s.tracer.events()
                 if e.get("ph") == "X"}
        # snapshot.build/sync/swap from model.publisher()'s publish, plus
        # the engine's flush/batch spans
        assert {"engine.flush", "engine.batch", "snapshot.build",
                "snapshot.swap"} <= names
        assert s.metrics.get("serve.queue_depth").value == 0
    finally:
        s.close(save=False)


def test_loader_prefetch_counters(stream_dir):
    from repro.data.stream import Cursor, StreamingLoader

    path, reader, _ = stream_dir
    s = obs.ObsSession(obs.ObsConfig(enabled=True)).install()
    try:
        loader = StreamingLoader(reader, seed=0)
        visits = list(loader.iterate(Cursor(), end_epoch=1))
        assert len(visits) == reader.num_shards
        hit = s.metrics.get("stream.prefetch_hit")
        miss = s.metrics.get("stream.prefetch_miss")
        total = (hit.value if hit else 0) + (miss.value if miss else 0)
        assert total == reader.num_shards
        assert s.metrics.get("stream.shard_wait_ms").count == total
        names = {e["name"] for e in s.tracer.events()
                 if e.get("ph") == "X"}
        assert {"stream.load", "stream.shard_wait"} <= names
    finally:
        s.close(save=False)


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

def _profiled_host_events(trace_dir, body):
    """Run ``body`` under ``jax.profiler`` and return the host events of
    the ``.xplane.pb`` it writes: {name: [(start_ns, end_ns)]}."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns))
    return events


@pytest.mark.parametrize("installed", [False, True])
def test_span_lands_in_the_profiler_trace_inside_its_caller(tmp_path,
                                                            installed):
    import jax

    def body():
        with jax.profiler.TraceAnnotation("caller"):
            with obs.span("test.inner", cat="test"):
                jax.numpy.arange(8).block_until_ready()

    sess = (obs.ObsSession(obs.ObsConfig(enabled=True)).install()
            if installed else None)
    try:
        events = _profiled_host_events(tmp_path, body)
    finally:
        if sess is not None:
            sess.close(save=False)
    (cs, ce), = events["caller"]
    (s, e), = events["test.inner"]
    assert cs <= s <= e <= ce
    if installed:                      # and the Chrome span as before
        assert [ev["name"] for ev in sess.tracer.events()
                if ev.get("ph") == "X"] == ["test.inner"]


def test_no_profiler_no_session_span_is_null():
    assert obs.annotation("x") is NULL_SPAN
    assert obs.span("x") is NULL_SPAN


def test_executor_dispatch_is_annotated_without_a_session(tmp_path,
                                                          lda_state):
    import jax
    from repro.train import async_exec

    _, cfg, state = lda_state(num_docs=80, vocab=128, k=8, num_shards=2,
                              block_tokens=256)
    step, _ = async_exec.make_executor(state, cfg, async_exec.ExecConfig())
    jax.block_until_ready(step(state, jax.random.PRNGKey(0)).z)   # compile
    assert obs.active() is None
    events = _profiled_host_events(
        tmp_path, lambda: jax.block_until_ready(
            step(state, jax.random.PRNGKey(1)).z))
    assert len(events["exec.dispatch"]) == 1


# ---------------------------------------------------------------------------
# the sweep's phases: scope table and the benchmark's readers of it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("blocks", [0, 4])
def test_executor_registers_its_step_once_by_name(lda_state, monkeypatch,
                                                  blocks):
    import jax
    from repro.obs import scopes
    from repro.train import async_exec

    monkeypatch.setattr(scopes, "_PROGRAMS", [])
    _, cfg, state = lda_state(num_docs=80, vocab=128, k=8, num_shards=2,
                              block_tokens=256)
    step, _ = async_exec.make_executor(
        state, cfg, async_exec.ExecConfig(model_blocks=blocks))
    for i in range(2):
        state = step(state, jax.random.PRNGKey(i))
    name = "pipelined_sweep" if blocks else "snapshot_sweep"
    assert scopes.registered() == [name]
    table = scopes.scope_table()
    assert set(table.values()) == set(scopes.PHASES) | {None}


COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")

SPMD_SCOPES = """
import json, sys
sys.path.insert(0, "src")
import jax
from repro import api
from repro.data import corpus as corpus_mod
from repro.obs import scopes

corp = corpus_mod.generate_lda_corpus(seed=0, num_docs=80, mean_doc_len=30,
                                      vocab_size=200, num_topics=6)
job = api.LDAJob(corpus=corp, num_topics=8, block_tokens=256,
                 backend=api.SPMD, mesh_model=2, sweeps=1, eval_every=0,
                 route=api.HybridRoute(hot_words=32, use_kernel=False))
state, step, _ = api.Session(job, log_fn=lambda *a: None).make_step()
jax.block_until_ready(step(state, jax.random.PRNGKey(0)).z)
table = scopes.scope_table(exchange=True)
(prog,) = scopes._PROGRAMS
text = prog["fn"].lower(*prog["args"]).compile().as_text()
print("RESULT " + json.dumps({
    "registered": scopes.registered(),
    "rows": [[i.name, i.opcode] + list(table[i.name])
             for i in scopes.instructions(text)]}))
"""


def test_spmd_scope_table_puts_every_collective_under_exchange():
    """The SPMD sweep on a (2, 2) mesh of forced host devices: every
    collective instruction lies under ``exchange``, inside the phase of
    the pull or the push it serves."""
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SPMD_SCOPES)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads([ln for ln in out.stdout.splitlines()
                      if ln.startswith("RESULT ")][-1][len("RESULT "):])
    assert got["registered"] == ["spmd_sweep"]
    coll = [r for r in got["rows"]
            if any(r[1].startswith(c) for c in COLLECTIVES)]
    assert {r[1] for r in coll} >= {"all-gather", "all-reduce"}, coll
    assert all(r[3] for r in coll), coll
    assert {r[2] for r in coll} == {"ps.pull", "ps.push"}, coll
    # what lies under exchange belongs to the pull or the push
    assert {r[2] for r in got["rows"] if r[3]} <= {"ps.pull", "ps.push"}


def test_one_chip_sweep_has_nothing_under_exchange(lda_state, monkeypatch):
    """In-process the collectives are the identity: the snapshot
    program compiles nothing under ``exchange``, and its phase table is
    the one it had."""
    import jax
    from repro.obs import scopes
    from repro.train import async_exec

    monkeypatch.setattr(scopes, "_PROGRAMS", [])
    _, cfg, state = lda_state(num_docs=80, vocab=128, k=8,
                              block_tokens=256)
    step, _ = async_exec.make_executor(
        state, cfg, async_exec.ExecConfig(hot_words=32))
    jax.block_until_ready(step(state, jax.random.PRNGKey(0)).z)
    table = scopes.scope_table(exchange=True)
    assert table and not any(e for _, e in table.values())
    assert scopes.scope_table() == {k: p for k, (p, _) in table.items()}


def test_scope_table_marks_names_two_programs_place_differently(
        monkeypatch):
    from repro.obs import scopes

    monkeypatch.setattr(scopes, "_PROGRAMS", [
        {"name": "a", "fn": None, "args": (),
         "table": {"fusion.1": "ps.pull", "add.2": "mh.chain",
                   "copy.3": None, "copy.4": None}},
        {"name": "b", "fn": None, "args": (),
         "table": {"fusion.1": "mh.chain", "add.2": "mh.chain",
                   "copy.3": None, "copy.4": "ps.push", "sort.5": None}}])
    assert scopes.scope_table() == {
        "fusion.1": scopes.AMBIGUOUS, "add.2": "mh.chain", "copy.3": None,
        "copy.4": scopes.AMBIGUOUS, "sort.5": None}


SCOPED_HLO = """HloModule jit_f

%fused_computation.1 (param_0: s32[8]) -> s32[64] {
  %param_0 = s32[8]{0} parameter(0)
  %negate.1 = s32[8]{0} negate(%param_0), metadata={op_name="jit(f)/while/body/ndk.merge/neg"}
  ROOT %scatter.1 = s32[64]{0} scatter(%param_0, %negate.1), to_apply=%add
}

ENTRY %main.9 (p0: s32[8], p1: s32[8]) -> (s32[64], s32[8]) {
  %p0 = s32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %fusion.1 = s32[64]{0} fusion(%p0), kind=kCustom, calls=%fused_computation.1
  %copy.1 = s32[64]{0} copy(%fusion.1)
  %slice-start.1 = ((s32[8]{0}), s32[8]{0}, s32[]) slice-start(%p1), slice={[0:8]}
  %slice-done.1 = s32[8]{0} slice-done(%slice-start.1)
  %add.1 = s32[8]{0} add(%slice-done.1, %p0), metadata={op_name="jit(f)/ps.push/add"}
  %add.2 = s32[8]{0} add(%p0, %p1), metadata={op_name="jit(f)/while/body/add"}
  %select.1 = s32[8]{0} select(%add.1, %negate.2, %p0)
  %negate.2 = s32[8]{0} negate(%p1), metadata={op_name="jit(f)/mh.chain/neg"}
  ROOT %tuple.1 = (s32[64]{0}, s32[8]{0}) tuple(%copy.1, %add.1)
}
"""


@pytest.mark.parametrize("name,phase", [
    ("negate.1", "ndk.merge"),       # its own op_name
    ("fusion.1", "ndk.merge"),       # the root of the computation it calls
    ("copy.1", "ndk.merge"),         # its operand
    ("slice-done.1", "ps.push"),     # its user
    ("slice-start.1", "ps.push"),    # its user's user
    ("add.2", None),                 # an op_name in no phase
    ("tuple.1", None),               # gathers values of every phase
])
def test_instruction_phase_rules(name, phase):
    from repro.obs import scopes

    got = {i.name: i for i in scopes.instructions(SCOPED_HLO)}
    assert got[name].phase == phase
    assert got["fusion.1"].computation == "main.9"
    assert got["scatter.1"].computation == "fused_computation.1"


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what each reader reads from SWEEP_OPS plus 0.0175 s of an operation the
# table does not know (under 1%), over 2 sweeps
PHASE_READERS = {"ps_pull_s.train": 0.05, "alias_tables_s.train": 0.1,
                 "mh_chain_s.train": 0.15, "ps_push_s.train": 0.3,
                 "ndk_merge_s.train": 0.2,
                 "unscoped_share.train": 100.0 * 0.2 / 1.8175,
                 "ps_push_s.train-spmd": 0.3,
                 "alias_tables_s.train-spmd": 0.1}


def _run_with_ops(monkeypatch, ops):
    """A traced run whose device plane holds ``ops`` ({text: seconds})
    over 2 sweeps, and whose program's scope table is fixed below."""
    import types

    from repro.obs import scopes

    monkeypatch.syspath_prepend(os.path.join(REPO, "perfbench"))
    import tracing
    monkeypatch.setattr(scopes, "scope_table", lambda: {
        "pull.1": "ps.pull", "alias_build.1": "alias.tables",
        "mh_sample.1": "mh.chain", "fusion.168": "ps.push",
        "fusion.165": "ndk.merge", "copy.70": None,
        "fusion.9": scopes.AMBIGUOUS})
    summary = tracing.TraceSummary(
        (0, 2 * 10**9),
        {"/device:TPU:0": {"busy": [(0, 10**9)],
                           "ops": {k: v * 1e9 for k, v in ops.items()}}},
        [])
    return types.SimpleNamespace(trace=summary, counters={"sweeps": 2})


def _read(name, run):
    import importlib.util

    path = os.path.join(REPO, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "test_reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


SWEEP_OPS = {"%pull.1 = s32[8,8]{1,0} fusion(%a)": 0.1,
             "%alias_build.1 = (f32[8,8]{1,0}, s32[8,8]{1,0}) "
             "custom-call(%a), custom_call_target=\"tpu_custom_call\"": 0.2,
             "%mh_sample.1 = s32[1,8]{1,0} custom-call(%a)": 0.3,
             "%fusion.168 = s32[64]{0} fusion(%a)": 0.6,
             "%fusion.165 = s32[64]{0} fusion(%a)": 0.4,
             "%copy.70 = s32[64]{0} copy(%a)": 0.2}


@pytest.mark.parametrize("name", sorted(PHASE_READERS))
def test_phase_readers_read_seconds_per_sweep(monkeypatch, name):
    ops = {**SWEEP_OPS, "%mystery.1 = s32[8]{0} add(%a, %b)": 0.0175}
    got = _read(name, _run_with_ops(monkeypatch, ops))
    # under 1% of the operation time unknown to the table: still read
    assert got == pytest.approx(PHASE_READERS[name], rel=1e-9)


@pytest.mark.parametrize("unknown", ["%mystery.1 = s32[8]{0} add(%a, %b)",
                                     "%fusion.9 = s32[8]{0} fusion(%a)",
                                     "not an instruction"])
def test_phase_readers_read_nothing_past_one_percent_unmatched(
        monkeypatch, unknown):
    run = _run_with_ops(monkeypatch, {**SWEEP_OPS, unknown: 0.03})
    for name in PHASE_READERS:
        assert _read(name, run) is None, name


def test_phase_readers_read_nothing_from_a_program_without_scopes(
        monkeypatch):
    import sys

    run = _run_with_ops(monkeypatch, SWEEP_OPS)
    monkeypatch.delattr(obs, "scopes", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    for name in PHASE_READERS:
        assert _read(name, run) is None, name


def _exchange_run(monkeypatch, ops, counters=None):
    """A traced run over 2 sweeps whose program places ``fusion.168``
    and ``all-gather.3`` under ``exchange``."""
    from repro.obs import scopes

    run = _run_with_ops(monkeypatch, ops)
    monkeypatch.setattr(scopes, "scope_table", lambda exchange=False: {
        "pull.1": ("ps.pull", False), "all-gather.3": ("ps.pull", True),
        "alias_build.1": ("alias.tables", False),
        "mh_sample.1": ("mh.chain", False), "fusion.168": ("ps.push", True),
        "fusion.165": ("ndk.merge", False), "copy.70": (None, False),
        "fusion.9": scopes.AMBIGUOUS})
    run.counters.update(counters or {})
    return run


EXCHANGE_OPS = {**SWEEP_OPS, "%all-gather.3 = s32[8,8]{1,0} "
                "all-gather(%a), dimensions={0}": 0.05}


def test_exchange_reader_reads_seconds_per_sweep_under_exchange(
        monkeypatch):
    run = _exchange_run(monkeypatch, EXCHANGE_OPS)
    assert _read("exchange_s.train-spmd", run) == pytest.approx(
        (0.6 + 0.05) / 2, rel=1e-9)


@pytest.mark.parametrize("unknown", ["%mystery.1 = s32[8]{0} add(%a, %b)",
                                     "%fusion.9 = s32[8]{0} fusion(%a)"])
def test_exchange_reader_reads_nothing_past_one_percent_unmatched(
        monkeypatch, unknown):
    run = _exchange_run(monkeypatch, {**EXCHANGE_OPS, unknown: 0.03})
    assert _read("exchange_s.train-spmd", run) is None


def test_exchange_readers_read_nothing_from_a_program_without_them(
        monkeypatch):
    # the scope table of a program with no exchange scope takes no
    # argument, and its steps give out no exchange bytes
    run = _run_with_ops(monkeypatch, EXCHANGE_OPS)
    assert _read("exchange_s.train-spmd", run) is None
    assert _read("exchange_bytes.train-spmd", run) is None


def test_exchange_bytes_reader_sums_the_programs_count(monkeypatch):
    run = _exchange_run(monkeypatch, EXCHANGE_OPS, {
        "exchange_bytes_per_sweep": {"pull_all_gather": 10, "hot_psum": 20,
                                     "cold_all_gather": 30, "nk_psum": 4}})
    assert _read("exchange_bytes.train-spmd", run) == 64.0


# ---------------------------------------------------------------------------
# shared bench timer
# ---------------------------------------------------------------------------

def test_time_loop_global_index_and_repeats():
    from repro.obs.timing import time_loop

    seen = []

    def step(carry, i):
        seen.append(i)
        return carry + 1

    carry, tm = time_loop(step, 0, iters=3, repeats=2, label="t")
    # warmup consumes global index 0; repeats continue the sequence
    assert seen == [0, 1, 2, 3, 4, 5, 6]
    assert carry == 7
    assert len(tm.times_s) == 2 and tm.best_s <= tm.mean_s
    assert tm.best_rate(10.0) == pytest.approx(30.0 / tm.best_s)


# ---------------------------------------------------------------------------
# obs_report
# ---------------------------------------------------------------------------

def test_obs_report_render_sections(tmp_path):
    from repro.launch import obs_report

    events = [
        {"name": "exec.sweep", "cat": "exec", "ph": "X", "pid": 1,
         "tid": 0, "ts": 0.0, "dur": 9000.0,
         "args": {"overlap_pct": 80.0}},
        {"name": "exec.sweep", "cat": "exec", "ph": "X", "pid": 1,
         "tid": 0, "ts": 9000.0, "dur": 11000.0,
         "args": {"overlap_pct": 60.0}},
        {"name": "ps.push", "cat": "ps", "ph": "X", "pid": 1, "tid": 0,
         "ts": 0.0, "dur": 2000.0,
         "args": {"route": "hybrid", "batch": 100, "dense_rows": 4,
                  "dense_bytes": 128, "coo_cap": 200, "coo_bytes": 2400}},
    ]
    with open(tmp_path / "trace.json", "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    for v in (1.0, 2.0, 3.0, 50.0):
        reg.histogram("serve.request_ms").record(v)
    reg.counter("stream.prefetch_hit").inc(5)
    reg.save(str(tmp_path / "metrics.jsonl"))

    text = obs_report.render(str(tmp_path))
    assert "exec.sweep" in text
    assert "mean=70.0%" in text                    # (80 + 60) / 2
    assert "hybrid" in text and "push routes" in text
    assert "serve.request_ms" in text
    assert "stream.prefetch_hit" in text


def test_obs_report_tolerates_empty_dir(tmp_path):
    from repro.launch import obs_report

    text = obs_report.render(str(tmp_path))
    assert "nothing recorded" in text


def test_obs_report_tier_section(tmp_path):
    from repro.launch import obs_report

    events = [
        {"name": "tier.miss_fetch", "cat": "ps", "ph": "X", "pid": 1,
         "tid": 0, "ts": 0.0, "dur": 1500.0,
         "args": {"rows": 32, "h2d_bytes": 8192}},
    ]
    with open(tmp_path / "trace.json", "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.gauge("ps.tier.hit_rate").set(0.953)
    reg.gauge("ps.tier.hot_rows").set(2048)
    reg.gauge("ps.tier.device_bytes").set(262144)
    reg.gauge("ps.tier.evictions").set(7)
    reg.save(str(tmp_path / "metrics.jsonl"))

    text = obs_report.render(str(tmp_path))
    assert "tiered storage" in text
    assert "hit_rate=0.953" in text and "hot_rows=2048" in text
    assert "32 rows" in text and "8.0 KiB H2D" in text
    # absent inputs -> no tier section (other runs unaffected)
    assert "tiered storage" not in obs_report.render(str(tmp_path),
                                                     trace_file="none.json",
                                                     metrics_file="none")


# ---------------------------------------------------------------------------
# satellites: TraceCallback, LogCallback, deprecation shims
# ---------------------------------------------------------------------------

def test_trace_callback_owns_session_when_job_untraced(tmp_path,
                                                       tiny_corpus):
    from repro import api

    out = tmp_path / "cb_obs"
    cb = api.TraceCallback(api.ObsConfig(enabled=True, out_dir=str(out)))
    api.Session(_tiny_job(tiny_corpus),
                log_fn=lambda *a, **k: None).run(callbacks=[cb])
    assert obs.active() is None                    # closed after the fit
    with open(out / "trace.json") as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    # the callback's own spans AND the executor's (ExecConfig.obs=None
    # inherits the callback-installed session)
    assert {"session.visit", "exec.sweep", "fit.start", "fit.end"} <= names


def test_log_callback_timestamps_and_flush(tmp_path):
    from repro.api.callbacks import LogCallback

    # path sink: every line durable and stamped with both clocks
    path = str(tmp_path / "log.jsonl")
    cb = LogCallback(path)
    cb.on_fit_start({"mode": "blocked", "staleness": 1})
    cb.on_fit_end(None)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert [ln["event"] for ln in lines] == ["fit_start", "fit_end"]
    for ln in lines:
        assert isinstance(ln["t_wall"], float)
        assert isinstance(ln["t_mono"], float)
    assert lines[1]["t_mono"] >= lines[0]["t_mono"]

    # file sink: flushed per write (readable before close)
    buf = io.StringIO()
    cb2 = LogCallback(buf)
    cb2.on_fit_start({"mode": "snapshot"})
    first = buf.getvalue()
    assert first.endswith("\n") and "t_mono" in first


def test_fit_lda_shims_warn_deprecation(lda_state, stream_dir):
    import jax
    from repro.core import lightlda as lda
    from repro.train import loop as train_loop
    from repro.train.async_exec import ExecConfig

    _, cfg, state = lda_state(num_docs=80, vocab=128, k=8, num_shards=2,
                              block_tokens=256)
    with pytest.warns(DeprecationWarning, match="fit_lda is deprecated"):
        train_loop.fit_lda(state, jax.random.PRNGKey(0), cfg, ExecConfig(),
                           sweeps=1, eval_every=0,
                           log_fn=lambda *a, **k: None)

    path, reader, corp = stream_dir
    scfg = lda.LDAConfig(num_topics=8, vocab_size=corp.vocab_size,
                         block_tokens=256, num_shards=2)
    with pytest.warns(DeprecationWarning,
                      match="fit_lda_stream is deprecated"):
        train_loop.fit_lda_stream(reader, scfg, ExecConfig(), epochs=1,
                                  max_shards=1,
                                  log_fn=lambda *a, **k: None)

def test_obs_report_network_section(tmp_path):
    from repro.launch import obs_report

    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    for op, n, bo, bi in (("pull_full", 10, 180, 4096000),
                          ("commit", 8, 512000, 160),
                          ("acquire", 12, 240, 600)):
        reg.counter(f"ps.rpc.calls.{op}").inc(n)
        reg.counter(f"ps.rpc.bytes_out.{op}").inc(bo)
        reg.counter(f"ps.rpc.bytes_in.{op}").inc(bi)
    reg.counter("ps.rpc.retries").inc(3)
    reg.counter("ps.rpc.reconnects").inc(2)
    for v in (0.5, 1.0, 8.0):
        reg.histogram("ps.rpc.ms.pull_full").record(v)
    reg.save(str(tmp_path / "metrics.jsonl"))

    text = obs_report.render(str(tmp_path))
    assert "network (ps.rpc transport" in text
    # ops ordered by call volume; traffic columns rendered
    assert text.index("acquire") < text.index("pull_full") < \
        text.index("commit")
    assert "retries=3" in text and "reconnects=2" in text
    assert "ps.rpc.ms.pull_full" in text      # histogram table picks it up
    # a run that never used the net backend: no section
    reg2 = MetricsRegistry()
    reg2.counter("stream.prefetch_hit").inc(5)
    reg2.save(str(tmp_path / "m2.jsonl"))
    assert "network (ps.rpc" not in obs_report.render(
        str(tmp_path), metrics_file="m2.jsonl")


def test_obs_report_admission_section(tmp_path):
    from repro.launch import obs_report

    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("serve.batch_trigger.full").inc(6)
    reg.counter("serve.batch_trigger.timeout").inc(2)
    reg.counter("serve.shed").inc(3)
    reg.gauge("serve.version_lag").set(1)
    reg.gauge("serve.snapshot_version").set(9)
    reg.save(str(tmp_path / "metrics.jsonl"))

    text = obs_report.render(str(tmp_path))
    assert "serving admission" in text
    assert "full=6 (75%)" in text and "timeout=2 (25%)" in text
    assert "shed=3" in text and "version_lag=1" in text
    assert "serving_version=9" in text
    # a run that never went through the concurrent plane: no section
    reg2 = MetricsRegistry()
    reg2.counter("stream.prefetch_hit").inc(5)
    reg2.save(str(tmp_path / "m2.jsonl"))
    assert "serving admission" not in obs_report.render(
        str(tmp_path), metrics_file="m2.jsonl")
