"""Algorithmic work of LightLDA's chain and Vose's build: lower bounds.

Each count is the least memory traffic (bytes) and arithmetic (flops) the
algorithm needs for the given shapes, whatever implements it -- never what
a kernel happens to move -- so a share of the roofline computed from it
cannot pass 100%, and a faster kernel raises it.  Scalars are 4 bytes
(int32 counts, float32 probabilities).  Random numbers are generated on
the chip and not counted as traffic.

Per token and sweep (``mh_sample``):

* read w, d and the current z, write the new z: 16 B (fold-in: w and z,
  12 B; its document is known from the batch layout);
* read the current topic's n_wk, n_dk and n_k once: 12 B (fold-in: n_wk
  and n_k, 8 B; a document's n_dk fits on the chip);
* per MH step, the word proposal reads one alias entry (probability and
  alias, 8 B) and the proposed topic's n_wk, n_dk, n_k (12 B); the doc
  proposal reads the topic of one token of the document (4 B) and the
  proposed topic's three counts (12 B); fold-in drops the n_dk reads;
* a token whose topic changed updates n_wk, n_dk and n_k for both its old
  and its new topic: 6 read-modify-writes, 48 B (training only);
* about 20 flops per proposal for the acceptance ratio.

Per alias build over R rows of K topics (``alias_build``): read the row's
counts (4 B) and n_k once, write the probability and the alias (8 B):
12 B per entry; about 4 flops per entry (scale, compare, one residual
update per retired entry).
"""
from __future__ import annotations

from typing import NamedTuple

SCALAR = 4
FLOPS_PER_PROPOSAL = 20


class Work(NamedTuple):
    bytes: float
    flops: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.bytes + other.bytes, self.flops + other.flops)

    def least_seconds(self, peaks: dict) -> float:
        """The larger of bytes over peak bandwidth and flops over peak
        rate: no chip can do the work faster."""
        return max(self.bytes / peaks["hbm_bytes_per_s"],
                   self.flops / peaks["bf16_flops_per_s"])


def mh_sample(tokens: float, mh_steps: int, changed: float = 0.0,
              frozen: bool = False) -> Work:
    """``tokens`` token resamples of ``mh_steps`` MH steps each, of which
    ``changed`` moved topic (training) -- or fold-in when ``frozen``."""
    if frozen:
        per_token = 3 * SCALAR + 2 * SCALAR + mh_steps * (
            (2 * SCALAR + 2 * SCALAR) + (SCALAR + 2 * SCALAR))
        updates = 0.0
    else:
        per_token = 4 * SCALAR + 3 * SCALAR + mh_steps * (
            (2 * SCALAR + 3 * SCALAR) + (SCALAR + 3 * SCALAR))
        updates = changed * 6 * 2 * SCALAR
    flops = tokens * mh_steps * 2 * FLOPS_PER_PROPOSAL
    return Work(tokens * per_token + updates, flops)


def alias_build(rows: float, topics: int) -> Work:
    """Vose alias tables for ``rows`` rows of ``topics`` entries."""
    entries = rows * topics
    return Work(entries * 3 * SCALAR + topics * SCALAR, entries * 4)


def sweep(tokens: float, changed: float, vocab: int, topics: int,
          mh_steps: int) -> Work:
    """One training sweep: the alias build over the vocabulary and the
    chain over every token (the count updates are in the chain's count;
    the pull and push of one chip's own table move nothing more)."""
    return (alias_build(vocab, topics)
            + mh_sample(tokens, mh_steps, changed=changed))
