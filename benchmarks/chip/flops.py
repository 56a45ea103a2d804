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

from typing import Any, Dict

from benchmarks.chip.weights import dims


def matmul_params(conf: Dict[str, Any]) -> int:
    """Weights that take part in a matrix product per token: every
    layer's projections and MLP, and the output head."""
    n = dims(conf)
    d, H, K, hd, f = n["d"], n["H"], n["K"], n["hd"], n["f"]
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = (3 if conf["activation_function"] == "silu" else 2) * d * f
    return n["L"] * (attn + mlp) + d * n["V"]


def attn_pair_flops(conf: Dict[str, Any]) -> int:
    """Operations per (query, key) pair, summed over heads and layers."""
    n = dims(conf)
    return 4 * n["H"] * n["hd"] * n["L"]


def train_flops_per_token(conf: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token of a causal sequence of ``seq``."""
    pairs_per_token = (seq + 1) / 2
    return 3 * (2 * matmul_params(conf)
                + attn_pair_flops(conf) * pairs_per_token)


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
