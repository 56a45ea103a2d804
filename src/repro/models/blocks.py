"""Scan-group execution: train/prefill forward and cached decode.

A :class:`repro.models.params.ScanGroup` holds ``depth`` identical layer
units, each a sequence of sublayers (e.g. ``("attn","mlp")`` or the 8-layer
Jamba period).  Parameters are stacked on a leading ``layers`` axis and the
unit is executed under ``jax.lax.scan`` — HLO size stays O(unique layers),
which keeps 126-layer compiles tractable.  Optional rematerialization wraps
the scan body with ``jax.checkpoint``.

The execution context ``ctx`` threads ``attn_impl`` ("naive" | "chunked" |
"pallas" — all differentiable, see :func:`repro.models.layers.run_attention`)
and ``remat`` from the train/eval step down to every attention sublayer, so
the jitted step — not the layer code — owns the kernel choice.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import mamba2, mla, moe
from repro.models.config import ModelConfig
from repro.models.layers import (attn_decode, attn_forward, mlp_forward,
                                 xattn_forward)
from repro.models.params import ScanGroup

PyTree = Any

REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


# --------------------------------------------------------------------------- #
# Train / prefill
# --------------------------------------------------------------------------- #

def _sublayer_train(kind: str, p: PyTree, x: jax.Array, aux: jax.Array,
                    cfg: ModelConfig, ctx: Dict[str, Any]):
    impl = ctx.get("attn_impl", "chunked")
    if kind == "attn":
        return attn_forward(p, x, cfg, positions=ctx["positions"],
                            causal=ctx.get("causal", True), impl=impl), aux
    if kind == "mla":
        return mla.mla_forward(p, x, cfg, positions=ctx["positions"],
                               causal=ctx.get("causal", True),
                               impl=impl), aux
    if kind == "mlp":
        return mlp_forward(p, x, cfg), aux
    if kind == "moe":
        y, l = moe.moe_forward(p, x, cfg)
        return y, aux + l
    if kind == "ssm":
        return mamba2.ssm_forward(p, x, cfg,
                                  use_kernel=ctx.get("use_kernel", False)), aux
    if kind == "xattn":
        return xattn_forward(p, x, ctx["enc"], cfg, impl=impl), aux
    raise ValueError(kind)


def group_forward(gparams: PyTree, group: ScanGroup, x: jax.Array,
                  cfg: ModelConfig, ctx: Dict[str, Any]
                  ) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden, aux_loss)."""
    from repro.distributed.act_sharding import BATCH, constrain

    def unit(p_unit: PyTree, carry):
        h, aux = carry
        for j, kind in enumerate(group.sublayers):
            h, aux = _sublayer_train(kind, p_unit[f"s{j}_{kind}"], h, aux,
                                     cfg, ctx)
            h = constrain(h, BATCH, None, None)   # keep batch-sharded in scan
        return h, aux

    aux0 = jnp.zeros((), jnp.float32)
    if group.depth == 1:
        return unit(gparams, (x, aux0))

    def body(carry, p_unit):
        return unit(p_unit, carry), None

    policy = REMAT_POLICIES.get(ctx.get("remat", "none"))
    if ctx.get("remat", "none") != "none":
        body = jax.checkpoint(body, policy=policy)
    (h, aux), _ = jax.lax.scan(body, (x, aux0), gparams)
    return h, aux


# --------------------------------------------------------------------------- #
# Decode (single token, cached)
# --------------------------------------------------------------------------- #

def init_sublayer_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                        dtype=jnp.bfloat16) -> PyTree:
    if kind == "attn":
        K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        return {"k": jnp.zeros((batch, max_len, K, hd), dtype),
                "v": jnp.zeros((batch, max_len, K, hd), dtype)}
    if kind == "mla":
        return mla.init_mla_cache(cfg, batch, max_len, dtype)
    if kind == "ssm":
        return mamba2.init_ssm_cache(cfg, batch)
    if kind == "xattn":
        # pre-projected encoder K/V (warmed once by model.warm_cross_cache)
        K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        T = cfg.encoder_seq_len
        return {"k": jnp.zeros((batch, T, K, hd), dtype),
                "v": jnp.zeros((batch, T, K, hd), dtype)}
    return {}   # stateless: mlp / moe


def _sublayer_decode(kind: str, p: PyTree, x: jax.Array, cache: PyTree,
                     cfg: ModelConfig, ctx: Dict[str, Any]):
    if kind == "attn":
        y, ck, cv = attn_decode(p, x, cfg, cache_k=cache["k"],
                                cache_v=cache["v"], index=ctx["index"],
                                positions=ctx["positions"])
        return y, {"k": ck, "v": cv}
    if kind == "mla":
        return mla.mla_decode(p, x, cfg, cache=cache, index=ctx["index"],
                              positions=ctx["positions"])
    if kind == "ssm":
        return mamba2.ssm_decode(p, x, cfg, cache=cache)
    if kind == "mlp":
        return mlp_forward(p, x, cfg), cache
    if kind == "moe":
        y, _ = moe.moe_forward(p, x, cfg)
        return y, cache
    if kind == "xattn":
        if ctx.get("enc") is not None:
            # legacy path: re-project encoder K/V this step (kept for
            # equivalence tests; the serve path uses the warmed cache)
            return xattn_forward(p, x, ctx["enc"], cfg), cache
        from repro.models.layers import xattn_decode
        return xattn_decode(p, x, cfg, cache_k=cache["k"],
                            cache_v=cache["v"]), cache
    raise ValueError(kind)


def init_group_cache(group: ScanGroup, cfg: ModelConfig, batch: int,
                     max_len: int, dtype=jnp.bfloat16) -> PyTree:
    unit = {f"s{j}_{kind}": init_sublayer_cache(kind, cfg, batch, max_len,
                                                dtype)
            for j, kind in enumerate(group.sublayers)}
    if group.depth == 1:
        return unit
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (group.depth,) + a.shape).copy(), unit)


# --------------------------------------------------------------------------- #
# Decode (single token, paged KV cache — the serving-engine path)
# --------------------------------------------------------------------------- #

PAGED_SUBLAYERS = ("attn", "mlp", "moe")


def init_paged_sublayer_cache(kind: str, cfg: ModelConfig, num_blocks: int,
                              block_size: int, dtype=jnp.bfloat16) -> PyTree:
    """Per-sublayer page pools.  Unlike the dense cache there is no batch
    dim — sequences share the pool through their block tables.  With
    ``dtype`` int8 the pools are quantized per (page slot, kv head)
    vector and carry fp32 ``k_scale``/``v_scale`` pools alongside —
    (head_dim + 4) / (2 * head_dim) of the bf16 KV bytes per block.
    Pages are head-major, (blocks, kv_heads, block_size, head_dim): the
    layout the flash-decode kernel tiles (``kernels/flash_attention/
    decode.py``)."""
    if kind == "attn":
        K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        dt = jnp.dtype(dtype)
        pools = {"k_pages": jnp.zeros((num_blocks, K, block_size, hd), dt),
                 "v_pages": jnp.zeros((num_blocks, K, block_size, hd), dt)}
        if dt == jnp.int8:
            pools["k_scale"] = jnp.ones((num_blocks, K, block_size),
                                        jnp.float32)
            pools["v_scale"] = jnp.ones((num_blocks, K, block_size),
                                        jnp.float32)
        return pools
    if kind in ("mlp", "moe"):
        return {}                                  # stateless
    raise NotImplementedError(
        f"paged decode supports sublayers {PAGED_SUBLAYERS}, got {kind!r} "
        "(SSM/MLA/xattn caches are not token-paged)")


def _sublayer_decode_paged(kind: str, p: PyTree, x: jax.Array, cache: PyTree,
                           cfg: ModelConfig, ctx: Dict[str, Any]):
    if kind == "attn":
        from repro.models.layers import attn_decode_paged
        return attn_decode_paged(
            p, x, cfg, cache=cache,
            block_tables=ctx["block_tables"], seq_lens=ctx["seq_lens"],
            positions=ctx["positions"], num_feed=ctx.get("num_feed"),
            impl=ctx.get("attn_impl", "gather"))
    if kind == "mlp":
        return mlp_forward(p, x, cfg), cache
    if kind == "moe":
        y, _ = moe.moe_forward(p, x, cfg)
        return y, cache
    raise NotImplementedError(kind)


def group_decode_paged(gparams: PyTree, group: ScanGroup, x: jax.Array,
                       cache: PyTree, cfg: ModelConfig, ctx: Dict[str, Any]
                       ) -> Tuple[jax.Array, PyTree]:
    def unit(p_unit: PyTree, c_unit: PyTree, h: jax.Array):
        new_c = {}
        for j, kind in enumerate(group.sublayers):
            key = f"s{j}_{kind}"
            h, new_c[key] = _sublayer_decode_paged(kind, p_unit[key], h,
                                                   c_unit[key], cfg, ctx)
        return h, new_c

    if group.depth == 1:
        return unit(gparams, cache, x)

    def body(h, xs):
        p_unit, c_unit = xs
        h, new_c = unit(p_unit, c_unit, h)
        return h, new_c

    h, new_cache = jax.lax.scan(body, x, (gparams, cache))
    return h, new_cache


def group_decode(gparams: PyTree, group: ScanGroup, x: jax.Array,
                 cache: PyTree, cfg: ModelConfig, ctx: Dict[str, Any]
                 ) -> Tuple[jax.Array, PyTree]:
    def unit(p_unit: PyTree, c_unit: PyTree, h: jax.Array):
        new_c = {}
        for j, kind in enumerate(group.sublayers):
            key = f"s{j}_{kind}"
            h, new_c[key] = _sublayer_decode(kind, p_unit[key], h,
                                             c_unit[key], cfg, ctx)
        return h, new_c

    if group.depth == 1:
        return unit(gparams, cache, x)

    def body(h, xs):
        p_unit, c_unit = xs
        h, new_c = unit(p_unit, c_unit, h)
        return h, new_c

    h, new_cache = jax.lax.scan(body, x, (gparams, cache))
    return h, new_cache
