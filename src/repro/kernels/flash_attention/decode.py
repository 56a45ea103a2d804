"""Pallas TPU flash-decode kernel over a paged KV cache.

Decode/chunked-prefill attention for serving: each query row attends to
its sequence's cached K/V, which live in fixed-size **pages** (see
``serve.paged_cache``) rather than one dense per-sequence buffer.

* **Block-table gather** — K/V pages are selected inside the BlockSpec
  index map from a scalar-prefetched ``(B, max_blocks)`` block table
  (``PrefetchScalarGridSpec``), so the kernel streams exactly the pages a
  sequence owns straight from the pool; no dense (B, T, K, D) gather is
  materialized in HBM.
* **Split-KV partial max/sum reduction** — the flash-decoding recipe: the
  page axis is split into ``num_splits`` ranges; each range reduces its
  pages online (fp32 running max/sum in VMEM scratch, exactly the FA-2
  forward update from ``kernel.py``) and emits partial ``(m, l, acc)``;
  a tiny jnp epilogue merges the partials with the standard logsumexp
  rescale.  On TPU the split axis gives the sequential grid short
  accumulation chains; in interpret mode it exercises the same math.
* **GQA + sliding-window block-skip** — the grid runs over KV heads; each
  program handles that head's ``group = H // K`` query rows.  Pages fully
  outside the valid range (beyond ``seq_len`` or entirely left of the
  sliding window) are predicated out with the same live-block discipline
  as ``kernel.py::_block_live`` — dead pages do no MXU work.
* **Chunked prefill** — q may carry ``C`` teacher-forced query rows per
  sequence (``(B, C, H, D)``); row ``c`` sits at cache position
  ``seq_lens - 1 + c`` and attends to ``seq_lens + c`` valid positions.
  The engine scatters all C rows' K/V before calling attention, so
  same-step causality is just the per-row length mask.  All rows of a
  sequence share the page stream — one grid, ``C * group`` query rows
  per program.
* **int8 KV** — with ``k_scale``/``v_scale`` pools of shape ``(P, K,
  bs)`` the pages hold int8 values quantized per (page slot, kv head)
  vector (``kernels/quant8`` blockwise scheme, quant block = head_dim);
  the kernel applies the scales in registers to the score and
  probability tiles (``s[:, t]·ks[t]``, ``(p·vs) @ v``), so HBM traffic
  stays at the int8 byte count.
* **Layout** — pages are head-major, ``(P, K, bs, D)``: one (kv head,
  page) block is a whole ``(bs, D)`` tile, which is what a TPU block's
  last two dims must be (multiples of (8, 128) or the whole dims); a
  ``(P, bs, K, D)`` pool would put a 1-wide head slice in the
  second-minor place, and pad every page ``K``-row slab to a full tile.
  The per-split row statistics leave the kernel lane-broadcast
  (``(..., CG, LANES)``) for the same reason.

``seq_lens`` counts **all** valid cache positions *including* the first
query row's token (the engine scatters the new K/V at position
``seq_len - 1`` before calling attention), so the first query position is
``seq_lens - 1`` and causality degenerates to the length mask.
``interpret=True`` runs the identical kernel logic on CPU (CI parity
tests vs ``chunked.py``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.kernel import LANES, NEG_INF

DEFAULT_PAGES_PER_SPLIT = 8


def _page_live(page, block_size: int, seq_len, *, window: int,
               chunk: int = 1):
    """Does logical ``page`` hold any position some query row may attend
    to?

    Mirrors ``kernel.py::_block_live`` for the decode case: row ``c`` of
    the chunk attends to positions ``< seq_len + c``, so the page is dead
    when it starts past the *last* row's valid length, or — with a
    sliding window — when its last position is already left of the
    *first* row's window (later rows' windows only extend further
    right)."""
    live = page * block_size < seq_len + (chunk - 1)
    if window > 0:
        live &= (page + 1) * block_size - 1 > seq_len - 1 - window
    return live


# --------------------------------------------------------------------------- #
# Reference (gather) path — also the CPU/XLA execution path for the engine
# --------------------------------------------------------------------------- #

def paged_attention_reference(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tables: jax.Array,
                              seq_lens: jax.Array, *, window: int = 0,
                              scale: Optional[float] = None,
                              k_scale: Optional[jax.Array] = None,
                              v_scale: Optional[jax.Array] = None
                              ) -> jax.Array:
    """Dense-gather oracle for the paged layout (fp32 softmax).

    q: (B, H, D) or (B, C, H, D) teacher-forced chunk rows; k/v_pages:
    (P, K, bs, D*); block_tables: (B, NB) int32; seq_lens: (B,) int32
    valid positions incl. the first query row's token (row ``c`` of a
    chunk attends to ``seq_lens + c`` positions).  ``k_scale``/``v_scale``
    ((P, K, bs) fp32) dequantize int8 pages.  Returns q's shape with D ->
    Dv.  Rows with seq_len == 0 return garbage (masked upstream) — padded
    engine slots are never read.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, C, H, D = q.shape
    P, K, bs, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    g = H // K
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    T = block_tables.shape[1] * bs

    def dense(pool):                  # (P, K, bs, ...) -> (B, T, K, ...)
        x = jnp.swapaxes(pool[block_tables], 2, 3)
        return x.reshape((B, T, K) + pool.shape[3:]).astype(jnp.float32)
    k, v = dense(k_pages), dense(v_pages)
    if k_scale is not None:
        k = k * dense(k_scale)[..., None]
    if v_scale is not None:
        v = v * dense(v_scale)[..., None]
    qf = q.reshape(B, C, K, g, D).astype(jnp.float32)
    s = jnp.einsum("bckgd,btkd->bckgt", qf, k) * scale
    t = jnp.arange(T)[None, None, :]
    valid = seq_lens[:, None, None] + jnp.arange(C)[None, :, None]
    ok = t < valid                                       # (B, C, T)
    if window > 0:
        ok &= t > (valid - 1) - window
    s = jnp.where(ok[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bckgt,btkd->bckgd", p, v)
    out = out.reshape(B, C, H, Dv).astype(q.dtype)
    return out[:, 0] if squeeze else out


# --------------------------------------------------------------------------- #
# Pallas kernel
# --------------------------------------------------------------------------- #

def _flash_decode_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, *rest,
                         scale: float, window: int, block_size: int,
                         pages_per_split: int, chunk: int, group: int,
                         quantized: bool):
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    si = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = sl_ref[b]
    page = si * pages_per_split + j
    live = _page_live(page, block_size, seq_len, window=window, chunk=chunk)

    def head_row(ref):
        """(1, bs) scales of kv head h from the page's (K, bs) block — a
        masked sublane sum, so no dynamic sublane slice is needed."""
        blk = ref[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
        return jnp.sum(jnp.where(rows == h, blk, 0.0), axis=0,
                       keepdims=True)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (C*g, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)             # (bs, Dv)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * head_row(ks_ref)                    # column t · ks[t]
        t = page * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # per-row valid length: row r belongs to chunk index r // group
        valid = seq_len + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // group
        ok = t < valid
        if window > 0:
            ok &= t > valid - 1 - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                              # (C*g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        pv = p * head_row(vs_ref) if quantized else p    # (p · vs) @ v
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            pv, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pages_per_split - 1)
    def _done():
        m_ref[0, 0, 0] = jnp.broadcast_to(m_scr[...], m_ref.shape[3:])
        l_ref[0, 0, 0] = jnp.broadcast_to(l_scr[...], l_ref.shape[3:])
        acc_ref[0, 0, 0] = acc_scr[...]


def _decode_bkgd(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                 block_tables: jax.Array, seq_lens: jax.Array, window: int,
                 scale: float, pages_per_split: int, interpret: bool,
                 chunk: int, group: int,
                 k_scale: Optional[jax.Array], v_scale: Optional[jax.Array]
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Runs the split-KV kernel.  q: (B, K, C*g, D) with rows ordered
    chunk-major.  Returns the per-split partials (m, l, acc) of shapes
    (B,K,S,CG) / (B,K,S,CG) / (B,K,S,CG,Dv)."""
    B, K, CG, D = q.shape
    bs = k_pages.shape[2]
    Dv = v_pages.shape[-1]
    nb = block_tables.shape[1]
    pps = min(pages_per_split, nb)
    num_splits = -(-nb // pps)
    quantized = k_scale is not None

    def page_of(si, j, bt, b):
        # clamp overhang pages of the last split onto a valid table entry;
        # they are predicated dead in the kernel (page*bs >= seq_len)
        return bt[b, jnp.minimum(si * pps + j, nb - 1)]

    grid = (B, K, num_splits, pps)
    kernel = functools.partial(
        _flash_decode_kernel, scale=scale, window=window, block_size=bs,
        pages_per_split=pps, chunk=chunk, group=group, quantized=quantized)

    in_specs = [
        pl.BlockSpec((1, 1, CG, D),
                     lambda b, h, si, j, bt, sl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D),
                     lambda b, h, si, j, bt, sl:
                     (page_of(si, j, bt, b), h, 0, 0)),
        pl.BlockSpec((1, 1, bs, Dv),
                     lambda b, h, si, j, bt, sl:
                     (page_of(si, j, bt, b), h, 0, 0)),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        # all K heads' scales of the page: a (1, bs) head row would be a
        # 1-wide second-minor block
        scale_spec = pl.BlockSpec(
            (1, K, bs),
            lambda b, h, si, j, bt, sl: (page_of(si, j, bt, b), 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    row_spec = pl.BlockSpec((1, 1, 1, CG, LANES),
                            lambda b, h, si, j, bt, sl: (b, h, si, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            row_spec,
            row_spec,
            pl.BlockSpec((1, 1, 1, CG, Dv),
                         lambda b, h, si, j, bt, sl: (b, h, si, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, Dv), jnp.float32),
        ],
    )
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, K, num_splits, CG, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, K, num_splits, CG, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, K, num_splits, CG, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables, seq_lens, *operands)
    return m[..., 0], l[..., 0], acc


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "pages_per_split", "interpret"))
def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_tables: jax.Array, seq_lens: jax.Array, *,
                       window: int = 0, scale: Optional[float] = None,
                       pages_per_split: int = DEFAULT_PAGES_PER_SPLIT,
                       interpret: Optional[bool] = None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Flash-decoding over paged KV.  q: (B, H, D), or (B, C, H, D) for a
    teacher-forced prefill chunk; pages: (P, K, bs, D*); block_tables:
    (B, NB) int32 page ids; seq_lens: (B,) int32 valid positions including
    the first query row's token (row ``c`` attends to ``seq_lens + c``).
    ``k_scale``/``v_scale`` ((P, K, bs) fp32) dequantize int8 pages in
    registers.  Returns q's shape with D -> Dv."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, C, H, D = q.shape
    K = k_pages.shape[1]
    Dv = v_pages.shape[-1]
    g = H // K
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # chunk-major rows: row c*g + gi is chunk index c of group lane gi,
    # matching the kernel's `row // group` valid-length recovery
    qg = q.reshape(B, C, K, g, D).transpose(0, 2, 1, 3, 4).reshape(
        B, K, C * g, D)
    m, l, acc = _decode_bkgd(qg, k_pages, v_pages,
                             block_tables.astype(jnp.int32),
                             seq_lens.astype(jnp.int32),
                             window, float(scale), pages_per_split,
                             interpret, C, g, k_scale, v_scale)
    # merge the split partials: standard flash-decoding logsumexp rescale.
    # all-dead splits emit (m=-inf, l=0, acc=0) and vanish here.
    g_m = jnp.max(m, axis=2)                                    # (B,K,CG)
    alpha = jnp.exp(m - g_m[:, :, None, :])                     # (B,K,S,CG)
    l_tot = jnp.sum(l * alpha, axis=2)                          # (B,K,CG)
    acc_tot = jnp.sum(acc * alpha[..., None], axis=2)           # (B,K,CG,Dv)
    out = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
    out = out.reshape(B, K, C, g, Dv).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, Dv).astype(q.dtype)
    return out[:, 0] if squeeze else out
