"""``correct`` must fail what it exists to catch.

At a size the Pallas interpreter runs on the CPU, for each kind of cell:

* the control (the plain reference in bfloat16 in the program's place)
  and each planted fault read above what a sound run reads (0); at these
  sizes the counts stay under 256, which bfloat16 holds exactly, so the
  control reads a few tokens where at the cells' own sizes it reads 14
  to 34, over the limit of 4;
* a whole run of the harness, the look for a chip skipped, comes out
  ``correct`` true on the program as it is, and false with the timed path
  broken underneath: a step that returns its state unchanged, half of the
  batch left unsampled, one token altered where it is produced.  One chip
  has no exchange between chips to leave out.
"""
import time

import jax
import jax.numpy as jnp
import pytest

import control
import harness
from conftest import SERVE, TINY_CONFIG, TINY_TRAFFIC, spec_with_serving

TRAIN = "nytimes-k1024.train"


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    # no persistent cache, and no program traced before a fault is planted
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    spec = spec_with_serving(harness.load_spec())
    monkeypatch.setattr(harness, "load_spec", lambda: spec)
    jax.clear_caches()
    yield
    jax.clear_caches()


def tiny(name):
    cell = harness.Cell(harness.load_spec(), name)
    cell.config = {**cell.config, **TINY_CONFIG}
    cell.traffic = {**cell.traffic, **TINY_TRAFFIC}
    return cell


def run(name, seed=7):
    return harness.run_cell(name, seed, 2, False, t0=time.perf_counter(),
                            require_tpu=False, config_override=TINY_CONFIG,
                            traffic_override=TINY_TRAFFIC)


def test_control_and_faults_fail_training_limits():
    r = control.train_readings(tiny(TRAIN), 3)
    assert r["count_mismatch"] == 0 and r["sound"] == 0
    assert r["checked"] > 1000
    for name in ("control", "unchanged", "half", "token"):
        assert r[name] > 0, (name, r)


def test_control_and_faults_fail_serving_limits():
    r = control.serve_readings(tiny(SERVE), 3, 2)
    assert r["lost"] == 0 and r["sound"] == 0 and r["checked"] >= 10
    for name in ("control", "unchanged", "half", "token"):
        assert r[name] > 0, (name, r)


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def _broken_sampler(monkeypatch, module, attr, fault):
    orig = getattr(module, attr)

    def broken(*args, **kw):
        z = orig(*args, **kw)
        z0 = args[1] if attr == "mh_sample" else args[2]
        if fault == "unchanged":
            return z0
        if fault == "half":
            return jnp.where(jnp.arange(z.shape[0]) % 2 == 1, z0, z)
        return z.at[0].set((z[0] + 1) % TINY_CONFIG["topics"])

    monkeypatch.setattr(module, attr, broken)


@pytest.mark.parametrize("fault", ["unchanged", "half", "token"])
def test_broken_training_step_is_not_correct(monkeypatch, fault):
    if fault == "unchanged":
        from repro import api
        make_step = api.Session.make_step

        def unchanged(self):
            state, step, info = make_step(self)
            return state, (lambda st, key: st), info

        monkeypatch.setattr(api.Session, "make_step", unchanged)
    else:
        from repro.kernels import ops
        _broken_sampler(monkeypatch, ops, "mh_sample", fault)
    res = run(TRAIN)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "token"])
def test_broken_fold_in_is_not_correct(monkeypatch, fault):
    from repro.core import lightlda
    _broken_sampler(monkeypatch, lightlda, "sample_tokens_frozen", fault)
    res = run(SERVE)
    assert not res["correct"], res["checks"]
