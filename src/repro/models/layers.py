"""Core layers: norms, rotary embeddings (RoPE / M-RoPE), attention.

Pure functions over param pytrees.  Attention supports:

* GQA (num_kv_heads < num_heads) via head-group broadcast,
* causal, bidirectional (encoder), and sliding-window (Mixtral) masks,
* full-sequence (train/prefill) and single-token decode against a KV cache,
* sequence-sharded decode (flash-decoding partial-softmax merge) is layered
  on top in ``repro.serve.context_parallel``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

PyTree = Any
NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #

def norm(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """RMSNorm / LayerNorm: statistics in f32, normalize-multiply in the
    input dtype (the (B,S,1) rsqrt factor is exact in f32; applying it in
    bf16 costs <1e-3 relative error).  Standard practice; measured neutral
    on the dry-run byte proxy — XLA canonicalizes the converts
    (EXPERIMENTS.md §Perf #9, refuted hypothesis)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + cfg.norm_eps).astype(dtype)
        return x * inv * p["scale"].astype(dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + cfg.norm_eps).astype(dtype)
    out = (x - mean.astype(dtype)) * inv
    return out * p["scale"].astype(dtype) + p["bias"].astype(dtype)


def rms_gate_norm(scale: jax.Array, x: jax.Array, gate: jax.Array,
                  eps: float) -> jax.Array:
    """Mamba2 gated RMSNorm: norm(x * silu(z))."""
    dtype = x.dtype
    xf = (x * jax.nn.silu(gate)).astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------------- #
# Rotary embeddings
# --------------------------------------------------------------------------- #

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, d/2)
    angles = angles[..., None, :]                      # (..., S, 1, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, ...]) -> jax.Array:
    """Qwen2-VL multimodal rotary embedding.

    positions: (3, ..., S) — (temporal, height, width) position ids.
    ``sections`` split the d/2 frequency dims among t/h/w; text tokens carry
    identical t=h=w ids so M-RoPE degenerates to 1-D RoPE for them.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                       # (d/2,)
    # per-frequency section index -> select t/h/w position stream
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.asarray(sections), total_repeat_length=d // 2)
    onehot = jax.nn.one_hot(sec_id, len(sections), dtype=jnp.float32)  # (d/2, C)
    # angles per stream: (C, ..., S, d/2) -> select stream per frequency
    ang = positions[..., None].astype(jnp.float32) * freqs
    angles = jnp.einsum("c...f,fc->...f", ang, onehot)
    angles = angles[..., None, :]                      # (..., S, 1, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def positional(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.pos_embedding == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.pos_embedding == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return x   # learned / sinusoidal handled at the embedding layer


# --------------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------------- #

def _mask_bias(q_len: int, kv_len: int, *, causal: bool, window: int,
               q_offset: jax.Array | int = 0) -> jax.Array:
    """(q_len, kv_len) additive mask bias in fp32."""
    q_pos = jnp.arange(q_len) + q_offset
    k_pos = jnp.arange(kv_len)
    ok = jnp.ones((q_len, kv_len), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, window: int = 0,
                   q_offset: jax.Array | int = 0,
                   kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Reference attention.  q: (B,S,H,D), k/v: (B,T,K,D) with H % K == 0.

    ``kv_valid_len`` masks cache positions >= valid length (decode).
    Softmax in fp32; output in q.dtype.
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = H // K
    qf = q.reshape(B, S, K, g, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bskgd,btkd->bkgst", qf, kf) / jnp.sqrt(D).astype(jnp.float32)
    bias = _mask_bias(S, T, causal=causal, window=window, q_offset=q_offset)
    logits = logits + bias
    if kv_valid_len is not None:
        valid = jnp.arange(T)[None, :] < kv_valid_len.reshape(-1, 1)
        logits = logits + jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, vf)
    return out.reshape(B, S, H, Dv).astype(q.dtype)


def _project_qkv(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig,
                 kv_x: Optional[jax.Array] = None):
    kv_in = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_in, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_in, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


ATTN_IMPLS = ("naive", "chunked", "pallas")


@jax.named_scope("attention_core")
def run_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, window: int = 0, impl: str = "chunked",
                  scale: Optional[float] = None) -> jax.Array:
    """Dispatch: naive oracle / chunked flash (XLA) / Pallas TPU kernel.

    All three are differentiable — "pallas" carries a fused FA-2 backward
    (interpret mode off-TPU), so every impl is a valid training path.
    Whatever implements it, its ops carry the ``attention_core`` scope in
    their HLO ``op_name`` (backward ops under a ``transpose(...)``).
    """
    if impl == "naive":
        return attention_core(q, k, v, causal=causal, window=window)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                      scale=scale)
    if impl != "chunked":
        raise ValueError(f"unknown attn_impl {impl!r}; expected one of "
                         f"{ATTN_IMPLS}")
    from repro.kernels.flash_attention.chunked import chunked_attention
    return chunked_attention(q, k, v, causal=causal, window=window,
                             scale=scale)


@jax.named_scope("attention")
def attn_forward(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig, *,
                 positions: jax.Array, causal: bool = True,
                 impl: str = "chunked") -> jax.Array:
    """Full-sequence self-attention sublayer (train / prefill)."""
    from repro.distributed.act_sharding import BATCH, constrain
    h = norm(p["norm"], x, cfg)
    q, k, v = _project_qkv(p, h, cfg)
    q = positional(q, positions, cfg)
    k = positional(k, positions, cfg)
    q = constrain(q, BATCH, None, "model", None)
    k = constrain(k, BATCH, None, "model", None)
    v = constrain(v, BATCH, None, "model", None)
    out = run_attention(q, k, v, causal=causal, window=cfg.sliding_window,
                        impl=impl)
    return x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def xattn_forward(p: Dict[str, Any], x: jax.Array, enc: jax.Array,
                  cfg: ModelConfig, impl: str = "chunked") -> jax.Array:
    """Cross-attention sublayer (whisper decoder)."""
    h = norm(p["norm"], x, cfg)
    q, k, v = _project_qkv(p, h, cfg, kv_x=enc)
    out = run_attention(q, k, v, causal=False, impl=impl)
    return x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def xattn_decode(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig, *,
                 cache_k: jax.Array, cache_v: jax.Array) -> jax.Array:
    """Cross-attention decode against PRE-PROJECTED encoder K/V.

    The encoder context is static during decode, so K/V are projected once
    at cache-warm time (``model.warm_cross_cache``) — the legacy path
    re-projected the full 1500-frame encoder every token, which was ~100%
    of whisper's decode FLOPs (EXPERIMENTS.md §Roofline)."""
    h = norm(p["norm"], x, cfg)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    out = attention_core(q, cache_k.astype(q.dtype),
                         cache_v.astype(q.dtype), causal=False)
    return x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def project_cross_kv(p: Dict[str, Any], enc: jax.Array, cfg: ModelConfig
                     ) -> Tuple[jax.Array, jax.Array]:
    """(k, v) for a single xattn sublayer from encoder output (B,T,d)."""
    k = jnp.einsum("bsd,dhk->bshk", enc, p["wk"].astype(enc.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc, p["wv"].astype(enc.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(enc.dtype)
        v = v + p["bv"].astype(enc.dtype)
    return k, v


def attn_decode(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig, *,
                cache_k: jax.Array, cache_v: jax.Array, index: jax.Array,
                positions: jax.Array):
    """Single-token decode.  x: (B,1,d).  cache_k/v: (B,T,K,D).

    Returns (y, new_cache_k, new_cache_v).
    """
    h = norm(p["norm"], x, cfg)
    q, k, v = _project_qkv(p, h, cfg)
    q = positional(q, positions, cfg)
    k = positional(k, positions, cfg)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), index, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), index, axis=1)
    B = x.shape[0]
    valid = jnp.full((B,), index + 1)
    window = cfg.sliding_window
    out = attention_core(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
                         causal=False, window=window, q_offset=index,
                         kv_valid_len=valid)
    y = x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, cache_k, cache_v


def attn_decode_paged(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig, *,
                      cache: Dict[str, jax.Array],
                      block_tables: jax.Array, seq_lens: jax.Array,
                      positions: jax.Array, impl: str = "gather",
                      num_feed: Optional[jax.Array] = None):
    """Decode / chunked-prefill step against a PAGED KV cache.  x: (B,C,d)
    — C teacher-forced rows per sequence (C == 1 is plain decode).

    ``cache`` holds the shared block pools: ``k_pages``/``v_pages`` of
    shape (P, K, bs, D), plus ``k_scale``/``v_scale`` ((P, K, bs) fp32)
    when the pools are int8 (per-vector quant via ``kernels.quant8``,
    applied at append time here and inverted inside the attention
    gather).  block_tables: (B, NB) int32 page ids; seq_lens: (B,) cache
    positions already written (row ``c`` lands at ``seq_lens[b] + c``).
    ``num_feed``: (B,) rows actually fed per sequence this step; rows
    past it scatter to the null page and their output is ignored.
    Inactive batch slots carry ``seq_lens == 0`` and block tables full of
    the null page — their scatter hits page 0 (never allocated) and their
    output is ignored.

    Returns (y, new_cache).
    """
    from repro.kernels.flash_attention.decode import (flash_decode_paged,
                                                     paged_attention_reference)
    from repro.kernels.quant8.ops import quantize_kv
    k_pages, v_pages = cache["k_pages"], cache["v_pages"]
    k_scale = cache.get("k_scale")
    v_scale = cache.get("v_scale")
    B, C, _ = x.shape
    bs = k_pages.shape[2]
    nb = block_tables.shape[1]
    h = norm(p["norm"], x, cfg)
    q, k, v = _project_qkv(p, h, cfg)
    q = positional(q, positions, cfg)
    k = positional(k, positions, cfg)
    # scatter row c's K/V into its page: position seq_len + c -> block
    # (seq_len + c) // bs, offset (seq_len + c) % bs.  Active slots own
    # disjoint pages, so indices collide only on the null page (inactive
    # slots / rows past num_feed) where any value is fine.
    pos_idx = seq_lens[:, None] + jnp.arange(C, dtype=seq_lens.dtype)[None, :]
    page_ids = jnp.take_along_axis(
        block_tables, jnp.clip(pos_idx // bs, 0, nb - 1), axis=1)   # (B, C)
    if num_feed is not None:
        fed = jnp.arange(C)[None, :] < num_feed[:, None]
        page_ids = jnp.where(fed, page_ids, 0)
    offs = pos_idx % bs
    if k_scale is not None:                    # int8 pools: quantize at append
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_pages = k_pages.at[page_ids, :, offs].set(kq)
        v_pages = v_pages.at[page_ids, :, offs].set(vq)
        k_scale = k_scale.at[page_ids, :, offs].set(ks)
        v_scale = v_scale.at[page_ids, :, offs].set(vs)
    else:
        k_pages = k_pages.at[page_ids, :, offs].set(k.astype(k_pages.dtype))
        v_pages = v_pages.at[page_ids, :, offs].set(v.astype(v_pages.dtype))
    valid = seq_lens + 1                 # incl. the first row just written
    window = cfg.sliding_window
    qf = q.astype(jnp.float32)
    qf = qf[:, 0] if C == 1 else qf            # (B,H,D) | (B,C,H,D)
    attn = flash_decode_paged if impl == "pallas" \
        else paged_attention_reference
    out = attn(qf, k_pages, v_pages, block_tables, valid, window=window,
               k_scale=k_scale, v_scale=v_scale)
    if C == 1:
        out = out[:, None]
    out = out.astype(x.dtype)                  # (B, C, H, Dv)
    y = x + jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    new_cache = {"k_pages": k_pages, "v_pages": v_pages}
    if k_scale is not None:
        new_cache.update(k_scale=k_scale, v_scale=v_scale)
    return y, new_cache


# --------------------------------------------------------------------------- #
# MLP
# --------------------------------------------------------------------------- #

@jax.named_scope("mlp")
def mlp_forward(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = norm(p["norm"], x, cfg)
    wi = p["wi"].astype(x.dtype)
    if cfg.mlp_activation == "silu":
        a = jax.nn.silu(h @ wi) * (h @ p["wg"].astype(x.dtype))
    else:
        a = jax.nn.gelu(h @ wi)
    return x + a @ p["wo"].astype(x.dtype)
