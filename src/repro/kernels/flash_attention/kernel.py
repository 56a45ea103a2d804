"""Pallas TPU flash-attention kernels (causal / sliding-window / GQA).

Forward: grid = (batch, q_heads, q_blocks, kv_blocks); the last grid dim is
sequential on TPU, so the online-softmax running stats (m, l) and the fp32
output accumulator live in VMEM scratch and are carried across kv blocks.
Q/K/V stream HBM→VMEM in (BLOCK_Q×D) / (BLOCK_K×D) tiles; BLOCK sizes are
multiples of 128 so the q·kᵀ and p·v contractions land on the MXU.  GQA is
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
outside the kernels.  Both backward kernels reuse the forward's
block-skipping predication (blocks strictly above the causal diagonal,
fully left of the sliding window, or entirely in padding do no MXU work),
so causal backward FLOPs also get the analytic 0.5 factor.

``flash_attention_bhsd`` carries a ``jax.custom_vjp`` wiring these
together; ``interpret=True`` runs the exact same kernel logic on CPU
(CI / gradient-parity tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# Per-row statistics (logsumexp, Δ) cross HBM as (..., S, LANES) arrays,
# the row's value broadcast over one lane tile: a TPU block's last two
# dims must be (8k, 128k) or whole, so a (1, 1, block_q) row block does
# not lower.  Residuals keep only lane 0.
LANES = 128


def _block_live(qi, ki, *, causal: bool, window: int, block_q: int,
                block_k: int, q_len: int, kv_len: int):
    """Predicate: does (q block qi, kv block ki) contain any unmasked pair?

    A kv block strictly above the causal diagonal (first k position > last
    q position), entirely left of the sliding window (last k position <=
    first q position - window), or fully inside padding is dead — no MXU
    work, no accumulator updates.  The grid still visits the block (TPU
    grids are dense) but the body is predicated out: for long causal
    sequences this halves kernel compute, matching the analytic 0.5 causal
    factor in core/flops.
    """
    live = (ki * block_k < kv_len) & (qi * block_q < q_len)
    if causal:
        live &= ki * block_k <= qi * block_q + block_q - 1
    if window > 0:
        live &= (ki + 1) * block_k - 1 > qi * block_q - window
    return live


def _tile_mask(qi, ki, *, causal: bool, window: int, block_q: int,
               block_k: int, kv_len: int):
    """(block_q, block_k) bool mask for the (qi, ki) tile."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    ok = k_pos < kv_len                                 # padding mask
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    return ok


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, causal: bool, window: int,
                   block_q: int, block_k: int, q_len: int, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = _block_live(qi, ki, causal=causal, window=window,
                       block_q=block_q, block_k=block_k,
                       q_len=q_len, kv_len=kv_len)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(qi, ki, causal=causal, window=window,
                        block_q=block_q, block_k=block_k, kv_len=kv_len)
        s = jnp.where(ok, s, NEG_INF)

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

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[...] + jnp.log(l),
                                         lse_ref.shape[2:])


def _fwd_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
              window: int, scale: float, block_q: int, block_k: int,
              interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Runs the forward kernel.  Returns (out (B,H,S,Dv), lse (B,H,S) f32)."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    group = H // K

    pad_q = (-S) % block_q
    pad_k = (-T) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sp, Tp = S + pad_q, T + pad_k

    grid = (B, H, Sp // block_q, Tp // block_k)
    kernel = functools.partial(
        _fa_fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, q_len=S, kv_len=T)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
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
                      dq_ref, dq_scr, *, scale: float, causal: bool,
                      window: int, block_q: int, block_k: int, q_len: int,
                      kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = _block_live(qi, ki, causal=causal, window=window,
                       block_q=block_q, block_k=block_k,
                       q_len=q_len, kv_len=kv_len)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)
        do = do_ref[0, 0].astype(jnp.float32)          # (bq, dv)
        lse = lse_ref[0, 0][:, :1]                     # (bq, 1)
        dlt = delta_ref[0, 0][:, :1]                   # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(qi, ki, causal=causal, window=window,
                        block_q=block_q, block_k=block_k, kv_len=kv_len)
        s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)                           # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt) * scale                    # (bq, bk)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                        causal: bool, window: int, block_q: int,
                        block_k: int, q_len: int, kv_len: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = _block_live(qi, ki, causal=causal, window=window,
                       block_q=block_q, block_k=block_k,
                       q_len=q_len, kv_len=kv_len)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)
        do = do_ref[0, 0].astype(jnp.float32)          # (bq, dv)
        lse = lse_ref[0, 0][:, :1]                     # (bq, 1)
        dlt = delta_ref[0, 0][:, :1]                   # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(qi, ki, causal=causal, window=window,
                        block_q=block_q, block_k=block_k, kv_len=kv_len)
        s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)                           # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt) * scale                    # (bq, bk)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_bhsd(q, k, v, out, lse, do, causal: bool, window: int,
              scale: float, block_q: int, block_k: int, interpret: bool):
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

    pad_q = (-S) % block_q
    pad_k = (-T) % block_k
    if pad_q:
        # do pads with zeros so padded q rows contribute nothing to dk/dv;
        # lse pads with 0 (NOT -inf: exp(s - lse) must stay finite there).
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sp, Tp = S + pad_q, T + pad_k
    nq, nk = Sp // block_q, Tp // block_k
    lse = jnp.broadcast_to(lse[..., None], (B, H, Sp, LANES))
    delta = jnp.broadcast_to(delta[..., None], (B, H, Sp, LANES))

    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, i, j: (b, h, i, 0))
    do_spec = pl.BlockSpec((1, 1, block_q, Dv),
                           lambda b, h, i, j: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, LANES),
                            lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, i, j, g=group: (b, h // g, j, 0))
    v_spec = pl.BlockSpec((1, 1, block_k, Dv),
                          lambda b, h, i, j, g=group: (b, h // g, j, 0))

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          q_len=S, kv_len=T),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dkdv: grid is (B, H, kv_blocks, q_blocks) — the q reduction runs
    # innermost so dk/dv accumulate in VMEM.  BlockSpec index maps receive
    # (b, h, ki, qi): kv-indexed operands use the 3rd grid dim, q-indexed
    # operands the 4th.
    qk_spec = pl.BlockSpec((1, 1, block_q, D),
                           lambda b, h, j, i: (b, h, i, 0))
    dok_spec = pl.BlockSpec((1, 1, block_q, Dv),
                            lambda b, h, j, i: (b, h, i, 0))
    rowk_spec = pl.BlockSpec((1, 1, block_q, LANES),
                             lambda b, h, j, i: (b, h, i, 0))
    kk_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, j, i, g=group: (b, h // g, j, 0))
    vk_spec = pl.BlockSpec((1, 1, block_k, Dv),
                           lambda b, h, j, i, g=group: (b, h // g, j, 0))

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_fa_bwd_dkdv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          q_len=S, kv_len=T),
        grid=(B, H, nk, nq),
        in_specs=[qk_spec, kk_spec, vk_spec, dok_spec, rowk_spec, rowk_spec],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Tp, Dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dq = dq[:, :, :S, :]
    # GQA group-sum: q head h wrote into row h; kv head h // group owns
    # heads [h*g, (h+1)*g) — contiguous, so a reshape-sum folds the group.
    dk = dk_h[:, :, :T, :].reshape(B, K, group, T, D).sum(axis=2)
    dv = dv_h[:, :, :T, :].reshape(B, K, group, T, Dv).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------- #
# custom_vjp wiring + public entry point
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out, _ = _fwd_bhsd(q, k, v, causal, window, scale, block_q, block_k,
                       interpret)
    return out


def _flash_core_fwd(q, k, v, causal, window, scale, block_q, block_k,
                    interpret):
    out, lse = _fwd_bhsd(q, k, v, causal, window, scale, block_q, block_k,
                         interpret)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, window, scale, block_q, block_k, interpret,
                    res, do):
    q, k, v, out, lse = res
    return _bwd_bhsd(q, k, v, out, lse, do, causal, window, scale,
                     block_q, block_k, interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         scale: Optional[float] = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False) -> jax.Array:
    """q: (B,H,S,D); k/v: (B,K,T,D).  Returns (B,H,S,Dv).  Differentiable
    (fused FA-2 Pallas backward via custom_vjp)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash_core(q, k, v, causal, window, float(scale),
                       block_q, block_k, interpret)
