"""Pallas TPU kernel: fused LightLDA Metropolis-Hastings chain.

The per-token proposal/acceptance chain is the sampler's compute hot-spot
(billions of tokens per iteration in the paper).  The host-side ``ops.py``
wrapper pre-gathers each token's count/alias rows (the "pull"), so this
kernel is *pure vector compute* on VMEM-resident tiles:

  grid        : (B / TB,) token tiles
  VMEM blocks : [TB, Kp] count/alias rows, [S, TB] pre-drawn randoms,
                [1, TB] assignments -- Kp is K padded to a multiple of 128
                so the one-hot selections land on VPU lanes.

The token tile TB is chosen from Kp (``token_tile``): four [TB, Kp] row
blocks are double-buffered and the chain keeps several [TB, Kp]
temporaries, so a fixed TB that fits at K=128 overflows VMEM at K=1024.

TPU adaptation (DESIGN.md section 2): a GPU implementation would thread one
token per lane with random gathers; on TPU every "gather a column per row"
becomes a one-hot masked reduction over the K lane dimension, which is a
dense [TB, Kp] vector op -- no scatter/gather hardware needed, and the same
trick serves nk lookups.  ``mh_steps`` is unrolled (it is 2-4 in practice).

Padding contract (maintained by ops.py): proposals (alias entries and
pre-drawn doc draws) are always < K, so the padded columns K..Kp-1 are never
selected by any one-hot; their contents are irrelevant.

Oracle: ``repro.core.lightlda.mh_chain`` (also re-exported in ref.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Elements of one [TB, Kp] row block the default tile aims for (1 MiB of
# f32): at Kp=1024 that is TB=256, which the v5e compiler fits in its
# default scoped VMEM with the chain's temporaries.
_BLOCK_ELEMS = 1 << 18
_VMEM_DEFAULT = 16 << 20


def token_tile(kp: int) -> int:
    """Token tile for lane-padded width ``kp``: 256 up to Kp=1024, then
    shrinking to the 128-lane floor of the [1, TB] per-token blocks."""
    return max(128, min(256, _BLOCK_ELEMS // kp))


def vmem_limit(tb: int, kp: int) -> int:
    """Scoped-VMEM request for one grid step: the double-buffered row
    blocks and temporaries scale with TB*Kp; never below the default."""
    return max(_VMEM_DEFAULT, 16 * tb * kp * 4 + (4 << 20))


def _mh_kernel(z0_ref, nwk_ref, ndk_ref, nk_ref, aprob_ref, aalias_ref,
               uw_ref, uwa_ref, zd_ref, uda_ref, out_ref, *,
               num_topics: int, alpha: float, beta: float, vbeta: float,
               mh_steps: int, frozen: bool = False):
    tb, kp = nwk_ref.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (tb, kp), 1)

    z0 = z0_ref[0, :]
    nwk = nwk_ref[...]
    ndk = ndk_ref[...]
    nk = nk_ref[0, :]
    aprob = aprob_ref[...]
    aalias = aalias_ref[...]

    def col(mat, k):
        """Select column k_i of row i as a masked lane reduction."""
        return jnp.sum(jnp.where(iota == k[:, None], mat, 0.0), axis=1)

    def nk_at(k):
        return jnp.sum(jnp.where(iota == k[:, None], nk[None, :], 0.0), axis=1)

    def p(k):
        # collapsed posterior factors with the -dw correction (w.r.t. z0);
        # frozen (fold-in) mode corrects only the local doc counts -- the
        # snapshot never contained this document's tokens.
        e = (k == z0).astype(jnp.float32)
        e_wk = 0.0 if frozen else e
        return ((col(ndk, k) - e + alpha) * (col(nwk, k) - e_wk + beta)
                / (nk_at(k) - e_wk + vbeta))

    def q_word(k):
        return (col(nwk, k) + beta) / (nk_at(k) + vbeta)

    def q_doc(k):
        return col(ndk, k) + alpha

    z = z0
    for s in range(mh_steps):
        # ---- word proposal via alias table (single-uniform trick) ----
        scaled = uw_ref[s, :] * num_topics
        bucket = jnp.minimum(scaled.astype(jnp.int32), num_topics - 1)
        coin = scaled - bucket.astype(jnp.float32)
        pa = col(aprob, bucket)
        al = col(aalias.astype(jnp.float32), bucket).astype(jnp.int32)
        z_prop = jnp.where(coin < pa, bucket, al)
        ratio = (p(z_prop) * q_word(z)) / (
            jnp.maximum(p(z), 1e-30) * jnp.maximum(q_word(z_prop), 1e-30))
        z = jnp.where(uwa_ref[s, :] < ratio, z_prop, z)

        # ---- doc proposal (pre-drawn; independent of chain state) ----
        z_prop = zd_ref[s, :]
        ratio = (p(z_prop) * q_doc(z)) / (
            jnp.maximum(p(z), 1e-30) * jnp.maximum(q_doc(z_prop), 1e-30))
        z = jnp.where(uda_ref[s, :] < ratio, z_prop, z)

    out_ref[0, :] = z


def mh_sample_call(z0, nwk_rows, ndk_rows, nk, aprob, aalias,
                   u_word, u_waccept, z_doc, u_daccept, *,
                   num_topics: int, vocab_size: int, alpha: float,
                   beta: float, mh_steps: int,
                   tile_tokens: Optional[int] = None,
                   interpret: bool = True, frozen: bool = False):
    """pallas_call wrapper (see module docstring for the layout contract).

    ``tile_tokens`` None takes ``token_tile(Kp)``.  ``frozen=True``
    compiles the inference-mode chain (fold-in against a frozen snapshot;
    -dw correction on doc counts only)."""
    b = z0.shape[1]
    kp = nwk_rows.shape[1]
    tb = min(tile_tokens or token_tile(kp), b)
    assert b % tb == 0, (b, tb)
    grid = (b // tb,)

    kern = functools.partial(
        _mh_kernel, num_topics=num_topics, alpha=alpha, beta=beta,
        vbeta=vocab_size * beta, mh_steps=mh_steps, frozen=frozen)

    tok1 = pl.BlockSpec((1, tb), lambda i: (0, i))     # [1, B] per-token
    rows = pl.BlockSpec((tb, kp), lambda i: (i, 0))    # [B, Kp] row blocks
    full = pl.BlockSpec((1, kp), lambda i: (0, 0))     # replicated nk
    rand = pl.BlockSpec((mh_steps, tb), lambda i: (0, i))

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tok1, rows, rows, full, rows, rows, rand, rand, rand, rand],
        out_specs=tok1,
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(tb, kp)),
        interpret=interpret,
        name="mh_sample_frozen" if frozen else "mh_sample",
    )(z0, nwk_rows, ndk_rows, nk, aprob, aalias,
      u_word, u_waccept, z_doc, u_daccept)
