"""Full-model forward passes: train loss, prefill, cached decode.

One entry point pair serves every architecture in the zoo:

* :func:`forward_train`  — tokens (+ optional frontend embeddings) -> loss
* :func:`decode_step`    — one new token against a KV/SSM cache

Batch dict keys (all optional except ``tokens``/``labels``):

``tokens``        (B, S) int32             decoder input ids
``labels``        (B, S) int32, -1 masked  next-token targets
``positions``     (B, S) or (3, B, S)      rope / M-RoPE position ids
``vision_embeds`` (B, Sv, d)               VLM frontend stub output (prepended)
``frames``        (B, Se, d)               audio frontend stub output (encoder)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import params as P
from repro.models.blocks import (PAGED_SUBLAYERS, group_decode,
                                 group_decode_paged, group_forward,
                                 init_group_cache, init_paged_sublayer_cache)
from repro.models.config import ModelConfig
from repro.models.layers import norm
from repro.models.params import _sinusoidal

PyTree = Any


# --------------------------------------------------------------------------- #
# Embedding / head
# --------------------------------------------------------------------------- #

def embed_tokens(params: PyTree, cfg: ModelConfig, tokens: jax.Array
                 ) -> jax.Array:
    emb = params["embed"]["tok"]
    return jnp.take(emb, tokens, axis=0).astype(jnp.dtype(cfg.compute_dtype))


def lm_logits(params: PyTree, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(h.dtype)
        logits = jnp.einsum("bsd,vd->bsv", h, w)
    else:
        logits = h @ params["lm_head"].astype(h.dtype)
    if cfg.padded_vocab_size != cfg.vocab_size:
        # vocab-padding mask (ModelConfig.vocab_pad_multiple): padded ids
        # never win softmax/argmax
        pad = jnp.arange(cfg.padded_vocab_size) >= cfg.vocab_size
        logits = jnp.where(pad, jnp.asarray(-1e30, logits.dtype), logits)
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Masked mean CE in fp32.  labels == -1 are ignored."""
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll) / denom, denom


def default_positions(batch: Dict[str, jax.Array], cfg: ModelConfig,
                      seq_len: int, bsz: int) -> jax.Array:
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (bsz, seq_len))
    if cfg.pos_embedding == "mrope":
        return jnp.broadcast_to(pos[None], (3, bsz, seq_len))
    return pos


# --------------------------------------------------------------------------- #
# Encoder (whisper)
# --------------------------------------------------------------------------- #

def encoder_forward(params: PyTree, cfg: ModelConfig, frames: jax.Array,
                    ctx: Dict[str, Any]) -> jax.Array:
    x = frames.astype(jnp.dtype(cfg.compute_dtype))
    B, S, d = x.shape
    x = x + jnp.asarray(_sinusoidal(S, d), x.dtype)[None]
    enc_ctx = dict(ctx, causal=False,
                   positions=jnp.broadcast_to(
                       jnp.arange(S, dtype=jnp.int32)[None], (B, S)))
    # whisper encoder uses absolute positions only; disable rope there
    for gi, g in enumerate(P.encoder_groups(cfg)):
        x, _ = group_forward(params["encoder"][f"g{gi}"], g, x, cfg, enc_ctx)
    return norm(params["encoder"]["final_norm"], x, cfg)


# --------------------------------------------------------------------------- #
# Decoder trunk
# --------------------------------------------------------------------------- #

def decoder_trunk(params: PyTree, cfg: ModelConfig, x: jax.Array,
                  ctx: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    for gi, g in enumerate(P.decoder_groups(cfg)):
        x, a = group_forward(params["decoder"][f"g{gi}"], g, x, cfg, ctx)
        aux = aux + a
    return norm(params["final_norm"], x, cfg), aux


def _mtp_loss(params: PyTree, cfg: ModelConfig, h: jax.Array,
              tokens: jax.Array, labels: jax.Array,
              ctx: Dict[str, Any]) -> jax.Array:
    """DeepSeek-V3 multi-token prediction (depth 1..k sequential blocks)."""
    from repro.models.blocks import _sublayer_train
    total = jnp.zeros((), jnp.float32)
    prev = h                                               # (B,S,d)
    for k in range(cfg.mtp_depth):
        mp = params["mtp"][f"d{k}"]
        shift = k + 1
        prev_trim = prev[:, :-1, :]
        emb_next = embed_tokens(params, cfg, tokens[:, shift:])
        merged = jnp.concatenate(
            [norm(mp["norm_prev"], prev_trim, cfg),
             norm(mp["norm_emb"], emb_next, cfg)], axis=-1)
        x = merged @ mp["proj"].astype(merged.dtype)
        pos = ctx["positions"]
        pos_k = pos[..., shift:] if pos.ndim <= 2 else pos[..., shift:]
        sub_ctx = dict(ctx, positions=pos_k)
        aux = jnp.zeros((), jnp.float32)
        for key, p_sub in sorted(mp["block"].items()):
            kind = key.split("_", 1)[1]
            x, aux = _sublayer_train(kind, p_sub, x, aux, cfg, sub_ctx)
        x = norm(params["final_norm"], x, cfg)
        logits = lm_logits(params, cfg, x)
        lbl = labels[:, shift:]
        loss_k, _ = cross_entropy(logits, lbl)
        total = total + loss_k
        prev = x
        tokens = tokens  # unchanged; next depth shifts further
    return total * cfg.mtp_loss_coef / max(cfg.mtp_depth, 1)


def forward_train(params: PyTree, cfg: ModelConfig,
                  batch: Dict[str, jax.Array], *,
                  remat: str = "none", attn_impl: str = "chunked"
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (scalar loss, metrics).

    ``attn_impl`` selects the attention kernel for every attention sublayer:
    "naive" (fp32 oracle), "chunked" (XLA flash twin, default), or "pallas"
    (fused TPU kernel with the FA-2 custom-VJP backward; interpret mode on
    CPU).  All three train — gradients flow through each impl.

    The step's device work is named by ``jax.named_scope``: ``embed``,
    ``attention`` (with ``attention_core`` inside), ``mlp`` and ``lm_head``
    here and in ``layers``, ``optimizer`` in ``train.step``.  The names
    reach each compiled instruction's ``op_name`` as a path component,
    bare or wrapped in ``jvp(...)``, backward ops under a
    ``transpose(...)`` component, so a device trace can be split by layer
    whatever implements it.
    """
    from repro.distributed.act_sharding import BATCH, constrain
    tokens = batch["tokens"]
    labels = batch["labels"]
    B = tokens.shape[0]

    if cfg.is_encoder_decoder:
        enc = encoder_forward(params, cfg, batch["frames"],
                              {"remat": remat, "attn_impl": attn_impl})
        with jax.named_scope("embed"):
            x = embed_tokens(params, cfg, tokens)
            if "pos" in params["embed"]:
                S = tokens.shape[1]
                x = x + params["embed"]["pos"][:S].astype(x.dtype)[None]
        S = tokens.shape[1]
        ctx = {"positions": default_positions(batch, cfg, S, B),
               "remat": remat, "attn_impl": attn_impl, "causal": True,
               "enc": enc}
    else:
        with jax.named_scope("embed"):
            x = embed_tokens(params, cfg, tokens)
            if "pos" in params["embed"]:
                x = x + params["embed"]["pos"][:tokens.shape[1]] \
                    .astype(x.dtype)[None]
        if "vision_embeds" in batch:
            v = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([v, x], axis=1)
        S = x.shape[1]
        ctx = {"positions": default_positions(batch, cfg, S, B),
               "remat": remat, "attn_impl": attn_impl, "causal": True}

    x = constrain(x, BATCH, None, None)
    h, aux = decoder_trunk(params, cfg, x, ctx)
    with jax.named_scope("lm_head"):
        logits = lm_logits(params, cfg, h)
        logits = constrain(logits, BATCH, None, "model")
        loss, n_tok = cross_entropy(logits, labels)
    metrics = {"ce_loss": loss, "aux_loss": aux, "tokens": n_tok}
    total = loss + aux
    if cfg.mtp_depth > 0:
        mtp = _mtp_loss(params, cfg, h, tokens, labels, ctx)
        metrics["mtp_loss"] = mtp
        total = total + mtp
    return total, metrics


def forward_logits(params: PyTree, cfg: ModelConfig,
                   batch: Dict[str, jax.Array], *,
                   attn_impl: str = "naive") -> jax.Array:
    """Full-sequence logits (tests / prefill scoring)."""
    from repro.distributed.act_sharding import BATCH, constrain
    tokens = batch["tokens"]
    B = tokens.shape[0]
    if cfg.is_encoder_decoder:
        enc = encoder_forward(params, cfg, batch["frames"],
                              {"attn_impl": attn_impl})
        x = embed_tokens(params, cfg, tokens)
        if "pos" in params["embed"]:
            x = x + params["embed"]["pos"][:tokens.shape[1]].astype(x.dtype)[None]
        ctx = {"positions": default_positions(batch, cfg, tokens.shape[1], B),
               "causal": True, "enc": enc, "attn_impl": attn_impl}
    else:
        x = embed_tokens(params, cfg, tokens)
        if "pos" in params["embed"]:
            x = x + params["embed"]["pos"][:tokens.shape[1]].astype(x.dtype)[None]
        if "vision_embeds" in batch:
            x = jnp.concatenate([batch["vision_embeds"].astype(x.dtype), x],
                                axis=1)
        ctx = {"positions": default_positions(batch, cfg, x.shape[1], B),
               "causal": True, "attn_impl": attn_impl}
    x = constrain(x, BATCH, None, None)
    h, _ = decoder_trunk(params, cfg, x, ctx)
    logits = lm_logits(params, cfg, h)
    return constrain(logits, BATCH, None, "model")


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> PyTree:
    cache: Dict[str, Any] = {}
    for gi, g in enumerate(P.decoder_groups(cfg)):
        cache[f"g{gi}"] = init_group_cache(g, cfg, batch, max_len, dtype)
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> PyTree:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype))


def warm_cross_cache(params: PyTree, cfg: ModelConfig, cache: PyTree,
                     enc: jax.Array) -> PyTree:
    """Fill every xattn sublayer's cache with pre-projected encoder K/V.

    Called once after ``init_cache`` when serving an encoder-decoder —
    decode steps then run ``xattn_decode`` against the cache instead of
    re-projecting the full encoder context every token."""
    from repro.models.layers import project_cross_kv
    cache = dict(cache)
    for gi, g in enumerate(P.decoder_groups(cfg)):
        gkey = f"g{gi}"
        for j, kind in enumerate(g.sublayers):
            if kind != "xattn":
                continue
            key = f"s{j}_{kind}"
            p = params["decoder"][gkey][key]
            if g.depth == 1:
                k, v = project_cross_kv(p, enc, cfg)
            else:
                k, v = jax.vmap(
                    lambda pl: project_cross_kv(pl, enc, cfg))(p)
            old = cache[gkey][key]
            cache[gkey] = dict(cache[gkey])
            cache[gkey][key] = {"k": k.astype(old["k"].dtype),
                                "v": v.astype(old["v"].dtype)}
    return cache


def paged_decode_supported(cfg: ModelConfig) -> bool:
    """Whether the paged serving engine can run this architecture: every
    decoder sublayer must be token-paged or stateless (attn/mlp/moe).
    SSM recurrent state, MLA latent caches and warmed cross-attention are
    not paged (their per-sequence state is O(1) or encoder-length)."""
    if cfg.is_encoder_decoder:
        return False
    return all(kind in PAGED_SUBLAYERS
               for g in P.decoder_groups(cfg) for kind in g.sublayers)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16) -> PyTree:
    """Per-layer page pools (no batch dim — sequences share the pool via
    their block tables; block 0 is the null page, see serve.paged_cache)."""
    cache: Dict[str, Any] = {}
    for gi, g in enumerate(P.decoder_groups(cfg)):
        unit = {f"s{j}_{kind}": init_paged_sublayer_cache(
                    kind, cfg, num_blocks, block_size, dtype)
                for j, kind in enumerate(g.sublayers)}
        if g.depth > 1:
            unit = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (g.depth,) + a.shape).copy(),
                unit)
        cache[f"g{gi}"] = unit
    return cache


def decode_step_paged(params: PyTree, cfg: ModelConfig, cache: PyTree,
                      tokens: jax.Array, block_tables: jax.Array,
                      seq_lens: jax.Array, *, attn_impl: str = "gather",
                      num_feed: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, PyTree]:
    """One decode / chunked-prefill step over a paged KV cache with
    PER-SEQUENCE positions.

    tokens: (B, C) int32 teacher-forced rows (C == 1 is plain decode);
    block_tables: (B, NB) int32 page ids; seq_lens: (B,) int32 cache
    positions already written — row ``c`` is written at position
    ``seq_lens[b] + c`` and attends to ``seq_lens[b] + c + 1`` valid
    positions (all C rows scatter before attention, so same-step
    causality is the per-row length mask).  ``num_feed``: (B,) rows
    actually fed per sequence; rows past it write to the null page and
    the returned logits come from row ``num_feed - 1`` (row ``C - 1``
    when omitted).  Unlike :func:`decode_step` there is no shared scalar
    ``index``: every sequence sits at its own offset, which is what
    continuous batching schedules.  Returns (logits (B, vocab), new cache).
    """
    B, C = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    pos_bc = seq_lens[:, None].astype(jnp.int32) \
        + jnp.arange(C, dtype=jnp.int32)[None, :]            # (B, C)
    if "pos" in params["embed"]:
        pos_tab = params["embed"]["pos"]
        idx = jnp.clip(pos_bc, 0, pos_tab.shape[0] - 1)
        x = x + jnp.take(pos_tab, idx, axis=0).astype(x.dtype)
    positions = pos_bc
    if cfg.pos_embedding == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, B, C))
    ctx: Dict[str, Any] = {"positions": positions,
                           "block_tables": block_tables,
                           "seq_lens": seq_lens,
                           "num_feed": num_feed,
                           "attn_impl": attn_impl}
    new_cache: Dict[str, Any] = {}
    for gi, g in enumerate(P.decoder_groups(cfg)):
        x, new_cache[f"g{gi}"] = group_decode_paged(
            params["decoder"][f"g{gi}"], g, x, cache[f"g{gi}"], cfg, ctx)
    h = norm(params["final_norm"], x, cfg)
    logits = lm_logits(params, cfg, h)
    if num_feed is None:
        return logits[:, C - 1, :], new_cache
    last = jnp.clip(num_feed - 1, 0, C - 1).astype(jnp.int32)
    return jnp.take_along_axis(
        logits, last[:, None, None], axis=1)[:, 0, :], new_cache


def decode_step(params: PyTree, cfg: ModelConfig, cache: PyTree,
                tokens: jax.Array, index: jax.Array, *,
                positions: Optional[jax.Array] = None,
                enc: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, PyTree]:
    """One decode step.  tokens: (B, 1) int32; index: scalar cache offset.

    Returns (logits (B, vocab), new cache).
    """
    B = tokens.shape[0]
    x = embed_tokens(params, cfg, tokens)
    if "pos" in params["embed"]:
        x = x + jax.lax.dynamic_slice_in_dim(
            params["embed"]["pos"], index, 1, axis=0).astype(x.dtype)[None, 0]
    if positions is None:
        positions = jnp.full((B, 1), index, jnp.int32)
        if cfg.pos_embedding == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, B, 1))
    ctx: Dict[str, Any] = {"index": index, "positions": positions}
    if enc is not None:
        ctx["enc"] = enc
    new_cache: Dict[str, Any] = {}
    for gi, g in enumerate(P.decoder_groups(cfg)):
        x, new_cache[f"g{gi}"] = group_decode(
            params["decoder"][f"g{gi}"], g, x, cache[f"g{gi}"], cfg, ctx)
    h = norm(params["final_norm"], x, cfg)
    logits = lm_logits(params, cfg, h)
    return logits[:, 0, :], new_cache
