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

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import alias_build, delta_push, mh_sample

V_NYT = 102_660          # NYTimes vocabulary (the serving alias build's rows)


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


@pytest.mark.parametrize("frozen", [False, True])
@pytest.mark.parametrize("k", [128, 1024])
def test_mh_sample_compiles(one_chip, k, frozen):
    s = _spec(one_chip)
    b = 2048
    f32, i32 = jnp.float32, jnp.int32

    def fn(*a):
        return mh_sample.mh_sample_call(
            *a, num_topics=k, vocab_size=V_NYT, alpha=0.1, beta=0.01,
            mh_steps=2, interpret=False, frozen=frozen)

    _compile(fn, s((1, b), i32), s((b, k), f32), s((b, k), f32),
             s((1, k), f32), s((b, k), f32), s((b, k), i32),
             s((2, b), f32), s((2, b), f32), s((2, b), i32), s((2, b), f32))


@pytest.mark.parametrize("k", [128, 1024])
def test_alias_build_compiles(one_chip, k):
    s = _spec(one_chip)
    v = 1024
    f32, i32 = jnp.float32, jnp.int32

    def fn(*a):
        return alias_build.alias_build_call(*a, num_cols=k, interpret=False)

    _compile(fn, s((v, k), f32), s((v, k), i32), s((v, k), i32),
             s((v, 1), i32), s((v, 1), i32))


def test_delta_push_compiles(one_chip):
    s = _spec(one_chip)
    tok = s((1, 8192), jnp.int32)

    def fn(*a):
        return delta_push.delta_push_call(*a, vocab_pad=2048, k_pad=1024,
                                          interpret=False)

    _compile(fn, tok, tok, tok, tok)


def test_delta_apply_coo_compiles(one_chip):
    s = _spec(one_chip)
    tok = s((1, 16384), jnp.int32)

    def fn(*a):
        return delta_push.delta_apply_coo_call(*a, vocab_pad=2048,
                                               k_pad=1024, interpret=False)

    _compile(fn, tok, tok, tok)
