"""Pallas TPU flash-attention kernels (causal / sliding-window / GQA).

Forward: grid = (batch, q_heads, q_blocks, kv_blocks); the last grid dim is
sequential on TPU, so the online-softmax running stats (m, l) and the fp32
output accumulator live in VMEM scratch and are carried across kv blocks.
Q/K/V stream HBM→VMEM in (block_q×D) / (block_k×D) tiles.  GQA is
expressed in the K/V index_map (head h reads kv head h // group) — no
broadcasted materialization of K/V.

Backward (Dao et al. flash-attention-2): the forward saves only O(S)
residuals — the output and the logsumexp — and the backward recomputes the
score tile p = exp(q·kᵀ·scale − lse) per block.  Two kernels, each with the
reduction axis innermost so the accumulator lives in VMEM scratch:

* ``dq``  — grid (B, H, q_blocks, kv_blocks): dq[i] = Σ_j ds_ij · k_j
* ``dkdv``— grid (B, H, kv_blocks, q_blocks): dk_j = Σ_i ds_ijᵀ · q_i,
  dv_j = Σ_i p_ijᵀ · do_i, accumulated per q-head; the GQA group-sum
  (H → K heads) happens outside the kernel.

with ds = p ⊙ (do·vᵀ − Δ) · scale and Δ = rowsum(do ⊙ out) computed once
outside the kernels.

Tiles.  Every grid step costs a fixed ~0.35 µs on a v5e whatever its tile
holds, so small tiles leave these kernels bound by the grid, not by the
MXU.  ``pick_tiles`` chooses each kernel's (block_q, block_k) from the
shapes alone: the largest multiple of 128 up to the kernel's target that
divides the 128-padded length, exceeds a sliding window by less than one
128-row tile, and keeps the kernel's double-buffered blocks, scratch and
f32 score-sized temporaries inside ``VMEM_BUDGET``.

Dead tiles.  ``_block_live`` is the one definition of a tile with any
unmasked pair; ``_kv_bounds`` and ``_q_bounds`` give its live interval
along either axis.  TPU grids are dense, so a dead tile is still a grid
step, but its body is predicated out and every index_map clamps the
streamed operand (K/V in the forward and dq, q/dO/lse/Δ in dk/dv) to the
nearest live block: the block index does not change, so the pipeline
issues no copy.  Only tiles that cross the causal diagonal, the window's
edge or the kv padding build the element mask (``_block_full``).

``flash_attention_bhsd`` carries a ``jax.custom_vjp`` wiring these
together; ``interpret=True`` runs the exact same kernel logic on CPU
(CI / gradient-parity tests).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Per-row statistics (logsumexp, Δ) cross HBM as (..., S, LANES) arrays,
# the row's value broadcast over one lane tile: a TPU block's last two
# dims must be (8k, 128k) or whole, so a (1, 1, block_q) row block does
# not lower.  Residuals keep only lane 0.
LANES = 128
# VMEM one kernel may fill, by ``_vmem_bytes``: the v5e compiler accepted
# every tile estimated at up to 32 MiB, and refused 40 MiB and above.
VMEM_BUDGET = 32 * 2 ** 20
# Largest (block_q, block_k) per kernel: the fastest of 128–1024 per side
# on a v5e, timed per kernel at opt-125m's (D 64) and qwen2-7b's (D 128,
# GQA 7) causal shapes (PERF.md §6).
TILE_TARGETS = {"fwd": (1024, 1024), "dq": (1024, 1024),
                "dkdv": (512, 1024)}
# f32 (block_q, block_k) temporaries each kernel's body keeps live: scores,
# probabilities, the mask's iotas (forward); plus dp and ds (backward).
_SCORE_TEMPS = {"fwd": 4, "dq": 6, "dkdv": 6}


class Tiles(NamedTuple):
    """(block_q, block_k) for each of the three kernels."""
    fwd: Tuple[int, int]
    dq: Tuple[int, int]
    dkdv: Tuple[int, int]


class _Geom(NamedTuple):
    """A kernel's static tiling of the (q_len × kv_len) score matrix."""
    causal: bool
    window: int
    block_q: int
    block_k: int
    q_len: int
    kv_len: int


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(kernel: str, bq: int, bk: int, d: int, dv: int,
                itemsize: int) -> int:
    """VMEM of one kernel at (bq, bk): double-buffered in/out blocks (each
    padded to the (8·4/itemsize, 128) tile), f32 scratch and score-sized
    f32 temporaries."""
    def tile(rows, cols, size):
        return (_round_up(rows, 8 * 4 // size) * _round_up(cols, LANES)
                * size)
    f32 = 4
    blocks = (tile(bq, d, itemsize) + tile(bk, d, itemsize)    # q, k, v,
              + tile(bk, dv, itemsize) + tile(bq, dv, itemsize))  # out|dO
    if kernel == "fwd":
        blocks += tile(bq, LANES, f32)                          # lse
        scratch = 2 * tile(bq, 1, f32) + tile(bq, dv, f32)
    elif kernel == "dq":
        scratch = tile(bq, d, f32)
        blocks += 2 * tile(bq, LANES, f32) + scratch            # lse, Δ, dq
    else:
        scratch = tile(bk, d, f32) + tile(bk, dv, f32)
        blocks += 2 * tile(bq, LANES, f32) + scratch     # lse, Δ, dk, dv
    return 2 * blocks + scratch + _SCORE_TEMPS[kernel] * tile(bq, bk, f32)


def _fit(length: int, target: int, window: int) -> int:
    """Largest multiple of 128 that is at most ``target``, divides the
    128-padded ``length`` and exceeds ``window`` (if any) by < 128."""
    padded = _round_up(length, 128)
    cap = min(target, padded)
    if window > 0:
        cap = min(cap, max(128, _round_up(window, 128)))
    return max(b for b in range(128, cap + 1, 128) if padded % b == 0)


def pick_tiles(S: int, T: int, D: int, Dv: int, *, window: int,
               dtype) -> Tiles:
    """Each kernel's (block_q, block_k) for q (.., S, D), k (.., T, D) and
    v (.., T, Dv) of ``dtype``: the kernel's target cut to the lengths and
    the window, then halved (the larger side first) until it fits the
    VMEM budget.  Causality does not change the tiles: a causal grid's
    dead tiles cost a step but no copy and no work."""
    itemsize = jnp.dtype(dtype).itemsize
    out = {}
    for kernel, (tq, tk) in TILE_TARGETS.items():
        bq, bk = _fit(S, tq, window), _fit(T, tk, window)
        while (_vmem_bytes(kernel, bq, bk, D, Dv, itemsize) > VMEM_BUDGET
               and max(bq, bk) > 128):
            if bq >= bk:
                bq = _fit(S, bq // 2, window)
            else:
                bk = _fit(T, bk // 2, window)
        out[kernel] = (bq, bk)
    return Tiles(**out)


def _kv_bounds(qi, g: _Geom):
    """First and last kv block holding a live pair for q block ``qi``."""
    q0 = qi * g.block_q
    lo = 0
    if g.window > 0:                  # the first query's oldest key
        lo = jnp.maximum(q0 - g.window + 1, 0) // g.block_k
    hi = (g.kv_len - 1) // g.block_k
    if g.causal:                      # the last query's own key
        hi = jnp.minimum(hi, (q0 + g.block_q - 1) // g.block_k)
    return lo, hi


def _q_bounds(ki, g: _Geom):
    """First and last q block holding a live pair for kv block ``ki``:
    ``_kv_bounds`` inverted (``tests/test_kernels.py`` holds them equal
    to ``_block_live``)."""
    k0 = ki * g.block_k
    lo = 0
    if g.causal:                      # the first key's own query
        lo = k0 // g.block_q
    hi = (g.q_len - 1) // g.block_q
    if g.window > 0:                  # the last key's last query
        hi = jnp.minimum(hi, (k0 + g.block_k + g.window - 2) // g.block_q)
    return lo, hi


def _block_live(qi, ki, g: _Geom):
    """Predicate: does (q block qi, kv block ki) contain any unmasked pair?

    A kv block strictly above the causal diagonal (first k position > last
    q position), entirely left of the sliding window (last k position <=
    first q position - window), or fully inside padding is dead — no MXU
    work, no accumulator updates, no copy of its streamed operand.
    """
    lo, hi = _kv_bounds(qi, g)
    return (qi * g.block_q < g.q_len) & (ki >= lo) & (ki <= hi)


def _block_full(qi, ki, g: _Geom):
    """Predicate: is every pair of the (qi, ki) tile unmasked?  Python
    ``True`` where no tile of the grid can be masked."""
    full = True
    if g.kv_len % g.block_k:
        full &= (ki + 1) * g.block_k <= g.kv_len
    if g.causal:                      # last key <= first query
        full &= (ki + 1) * g.block_k - 1 <= qi * g.block_q
    if g.window > 0:                  # first key > last query - window
        full &= ki * g.block_k > qi * g.block_q + g.block_q - 1 - g.window
    return full


def _tile_mask(qi, ki, g: _Geom):
    """(block_q, block_k) bool mask for the (qi, ki) tile."""
    shape = (g.block_q, g.block_k)
    q_pos = qi * g.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = ki * g.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = k_pos < g.kv_len                               # padding mask
    if g.causal:
        ok &= k_pos <= q_pos
    if g.window > 0:
        ok &= k_pos > q_pos - g.window
    return ok


def _live_tiles(body, qi, ki, g: _Geom):
    """Runs ``body(mask)`` on a live tile; ``mask`` is the tile's element
    mask where it has masked pairs, else None."""
    live = _block_live(qi, ki, g)
    full = _block_full(qi, ki, g)
    if full is True:
        pl.when(live)(lambda: body(None))
        return
    pl.when(live & full)(lambda: body(None))
    pl.when(live & jnp.logical_not(full))(
        lambda: body(_tile_mask(qi, ki, g)))


def _clamp(i, bounds):
    lo, hi = bounds
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _scores(q, k, scale, mask):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _pad_seq(x, pad: int):
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, g: _Geom):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(mask):
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)
        s = _scores(q, k, scale, mask)

        m_prev = m_scr[...]                             # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    _live_tiles(_compute, qi, ki, g)

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[...] + jnp.log(l),
                                         lse_ref.shape[2:])


def _fwd_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
              window: int, scale: float, tiles: Tiles,
              interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Runs the forward kernel.  Returns (out (B,H,S,Dv), lse (B,H,S) f32)."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    group = H // K
    block_q, block_k = tiles.fwd

    pad_q, pad_k = (-S) % block_q, (-T) % block_k
    q = _pad_seq(q, pad_q)
    k, v = _pad_seq(k, pad_k), _pad_seq(v, pad_k)
    Sp, Tp = S + pad_q, T + pad_k
    g = _Geom(causal, window, block_q, block_k, S, T)

    def kv_map(b, h, qi, ki):
        return (b, h // group, _clamp(ki, _kv_bounds(qi, g)), 0)

    def q_map(b, h, qi, ki):
        return (b, h, qi, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fa_fwd_kernel, scale=scale, g=g),
        grid=(B, H, Sp // block_q, Tp // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), q_map),
            pl.BlockSpec((1, 1, block_q, LANES), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S, :], lse[:, :, :S, 0]


# --------------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------------- #

def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, scale: float, g: _Geom):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(mask):
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)
        do = do_ref[0, 0].astype(jnp.float32)          # (bq, dv)
        lse = lse_ref[0, 0][:, :1]                     # (bq, 1)
        dlt = delta_ref[0, 0][:, :1]                   # (bq, 1)

        p = jnp.exp(_scores(q, k, scale, mask) - lse)  # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt) * scale                    # (bq, bk)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _live_tiles(_compute, qi, ki, g)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                        g: _Geom):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(mask):
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)
        do = do_ref[0, 0].astype(jnp.float32)          # (bq, dv)
        lse = lse_ref[0, 0][:, :1]                     # (bq, 1)
        dlt = delta_ref[0, 0][:, :1]                   # (bq, 1)

        p = jnp.exp(_scores(q, k, scale, mask) - lse)  # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt) * scale                    # (bq, bk)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _live_tiles(_compute, qi, ki, g)

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq(q, k, v, do, lse, delta, group: int, scale: float, g: _Geom,
            interpret: bool):
    """dq (B,H,S,D) f32 from operands padded to the dq kernel's tiles."""
    B, H, Sp, D = q.shape
    Tp, Dv = k.shape[2], v.shape[-1]
    bq, bk = g.block_q, g.block_k

    def q_map(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, ki):
        return (b, h // group, _clamp(ki, _kv_bounds(qi, g)), 0)

    return pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, g=g),
        grid=(B, H, Sp // bq, Tp // bk),
        in_specs=[pl.BlockSpec((1, 1, bq, D), q_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bk, Dv), kv_map),
                  pl.BlockSpec((1, 1, bq, Dv), q_map),
                  pl.BlockSpec((1, 1, bq, LANES), q_map),
                  pl.BlockSpec((1, 1, bq, LANES), q_map)],
        out_specs=pl.BlockSpec((1, 1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _bwd_dkdv(q, k, v, do, lse, delta, group: int, scale: float, g: _Geom,
              interpret: bool):
    """Per-q-head (dk, dv), (B,H,T,D) f32, from operands padded to the
    dk/dv kernel's tiles.  The grid is (B, H, kv_blocks, q_blocks): the q
    reduction runs innermost so dk/dv accumulate in VMEM."""
    B, H, Sp, D = q.shape
    Tp, Dv = k.shape[2], v.shape[-1]
    bq, bk = g.block_q, g.block_k

    def q_map(b, h, ki, qi):
        return (b, h, _clamp(qi, _q_bounds(ki, g)), 0)

    def kv_map(b, h, ki, qi):
        return (b, h // group, ki, 0)

    def out_map(b, h, ki, qi):
        return (b, h, ki, 0)

    return pl.pallas_call(
        functools.partial(_fa_bwd_dkdv_kernel, scale=scale, g=g),
        grid=(B, H, Tp // bk, Sp // bq),
        in_specs=[pl.BlockSpec((1, 1, bq, D), q_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bk, Dv), kv_map),
                  pl.BlockSpec((1, 1, bq, Dv), q_map),
                  pl.BlockSpec((1, 1, bq, LANES), q_map),
                  pl.BlockSpec((1, 1, bq, LANES), q_map)],
        out_specs=[pl.BlockSpec((1, 1, bk, D), out_map),
                   pl.BlockSpec((1, 1, bk, Dv), out_map)],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tp, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, Tp, Dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _bwd_bhsd(q, k, v, out, lse, do, causal: bool, window: int,
              scale: float, tiles: Tiles, interpret: bool):
    """FA-2 backward from O(S) residuals.  Returns (dq, dk, dv) in the
    primal dtypes."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    group = H // K

    # Δ = rowsum(do ⊙ out) — the only residual not saved by the forward;
    # O(S·D) elementwise, cheaper than a dedicated preprocess kernel.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # (B,H,S)

    def operands(block_q, block_k):
        # do pads with zeros so padded q rows contribute nothing to dk/dv;
        # lse pads with 0 (NOT -inf: exp(s - lse) must stay finite there).
        pad_q, pad_k = (-S) % block_q, (-T) % block_k
        rows = [jnp.broadcast_to(_pad_seq(x[..., None], pad_q),
                                 (B, H, S + pad_q, LANES))
                for x in (lse, delta)]
        return (_pad_seq(q, pad_q), _pad_seq(k, pad_k), _pad_seq(v, pad_k),
                _pad_seq(do, pad_q), *rows)

    dq = _bwd_dq(*operands(*tiles.dq), group, scale,
                 _Geom(causal, window, *tiles.dq, S, T), interpret)
    dk_h, dv_h = _bwd_dkdv(*operands(*tiles.dkdv), group, scale,
                           _Geom(causal, window, *tiles.dkdv, S, T),
                           interpret)

    dq = dq[:, :, :S, :]
    # GQA group-sum: q head h wrote into row h; kv head h // group owns
    # heads [h*g, (h+1)*g) — contiguous, so a reshape-sum folds the group.
    dk = dk_h[:, :, :T, :].reshape(B, K, group, T, D).sum(axis=2)
    dv = dv_h[:, :, :T, :].reshape(B, K, group, T, Dv).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------- #
# custom_vjp wiring + public entry point
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, window, scale, tiles, interpret):
    out, _ = _fwd_bhsd(q, k, v, causal, window, scale, tiles, interpret)
    return out


def _flash_core_fwd(q, k, v, causal, window, scale, tiles, interpret):
    out, lse = _fwd_bhsd(q, k, v, causal, window, scale, tiles, interpret)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, window, scale, tiles, interpret, res, do):
    q, k, v, out, lse = res
    return _bwd_bhsd(q, k, v, out, lse, do, causal, window, scale, tiles,
                     interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: bool = False) -> jax.Array:
    """q: (B,H,S,D); k/v: (B,K,T,D).  Returns (B,H,S,Dv).  Differentiable
    (fused FA-2 Pallas backward via custom_vjp).

    Tiles come from ``pick_tiles``; ``block_q`` / ``block_k`` (multiples of
    128) override that side of all three kernels' tiles."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    tiles = pick_tiles(q.shape[2], k.shape[2], q.shape[3], v.shape[3],
                       window=window, dtype=q.dtype)
    tiles = Tiles(*((block_q or bq, block_k or bk) for bq, bk in tiles))
    return _flash_core(q, k, v, causal, window, float(scale), tiles,
                       interpret)
