"""Operations and bytes the work requires, counted from shapes.

Conventions, fixed here so that no change of implementation moves them:

* a matrix product of (m, k) by (k, n) is 2 m k n operations;
* the input embedding is a gather and counts nothing; the output head is
  a matrix product and counts in full;
* a training step is forward plus backward, 3 times the forward's
  matrix products; nothing recomputed counts;
* causal attention over a sequence of S counts the S (S + 1) / 2 pairs
  of query and key it needs: 2 hd operations for the score and 2 hd for
  the weighted value, per pair and head;
* bytes are what a kernel must read and write at least: its inputs and
  outputs, once.
"""

from __future__ import annotations

from typing import Dict


def attention_cost(B: int, S: int, H: int, K: int, hd: int, *,
                   io_bytes: int = 2, backward: bool = False):
    """(operations, bytes) of one causal attention call over (B, S) with H
    query heads and K key/value heads of size hd.

    Forward: reads q, k, v and writes o and the f32 log-sum-exp of each
    row.  Backward: twice the forward's products (the gradients of the
    scores and of the values, then of q and k); reads q, k, v, o, do and
    the log-sum-exp, writes dq, dk, dv."""
    pairs = S * (S + 1) / 2
    flops = 4 * B * H * hd * pairs
    q = B * S * H * hd * io_bytes
    kv = B * S * K * hd * io_bytes
    lse = B * H * S * 4
    if not backward:
        return flops, q + 2 * kv + q + lse
    return 2 * flops, (q + 2 * kv + 2 * q + lse) + (q + 2 * kv)


def roofline_s(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
