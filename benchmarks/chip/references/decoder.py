"""Plain reference of a decoder-only transformer, in float32.

Written from the published descriptions of the two decoder families it
covers, and from nothing in the program under test:

* OPT (arXiv:2205.01068): pre-LayerNorm blocks, learned absolute
  positions added to the token embedding, multi-head attention with
  biases on q/k/v, a two-matrix MLP, a final LayerNorm and an output head
  tied to the token embedding.
* Qwen2 (arXiv:2407.10671): pre-RMSNorm blocks, rotary positions
  (half-split rotation, base ``rope_theta``), grouped-query attention
  with biases on q/k/v, a SwiGLU MLP, a final RMSNorm and an untied head.

Weights arrive as the decoder family's canonical dictionary
(``families/decoder.py``): ``embed`` (V, d), ``pos`` (P, d) where
positions are learned, ``lm_head`` (d, V) where untied, ``final_norm``,
and per layer ``attn_norm``, ``wq`` (d, H, hd), ``wk``/``wv`` (d, K, hd),
``bq`` (H, hd), ``bk``/``bv`` (K, hd), ``wo`` (H, hd, d), ``mlp_norm``,
``wi`` (d, f), ``wg`` (d, f) for a gated MLP, ``wo_mlp`` (f, d).

Every matrix product goes through :func:`mm`, which computes in float32
at the highest precision the chip offers, or, for the lower-precision
control, rounds both operands to float8 (e4m3, one scale per tensor) and
multiplies those in float32.  Nothing else changes between the two.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Weights = Dict[str, Any]
F8_MAX = 240.0          # largest finite value with 4 exponent, 3 mantissa bits
ATTN_ROWS = 1024        # query rows per block of attention scores


def _fp8(x: jax.Array) -> jax.Array:
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = F8_MAX / amax
    return jax.lax.reduce_precision(x * s, exponent_bits=4,
                                    mantissa_bits=3) / s


@jax.custom_vjp
def round_fp8(x: jax.Array) -> jax.Array:
    """x rounded to float8 e4m3 (4 exponent bits, 3 mantissa bits) under
    one per-tensor scale that maps its largest magnitude to the format's
    largest finite value, back in float32; the gradient flowing back is
    rounded the same way, as float8 training rounds it.  XLA's
    reduce-precision does the rounding, on any chip."""
    return _fp8(x)


def _round_fwd(x):
    return _fp8(x), None


def _round_bwd(_, g):
    return (_fp8(g),)


round_fp8.defvjp(_round_fwd, _round_bwd)


def mm(spec: str, a: jax.Array, b: jax.Array, lowp: bool) -> jax.Array:
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if lowp:
        a, b = round_fp8(a), round_fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def gated(conf: Dict[str, Any]) -> bool:
    return conf["activation_function"] == "silu"


def norm(p: Weights, x: jax.Array, conf: Dict[str, Any]) -> jax.Array:
    eps = conf["layer_norm_eps"]
    if conf["norm"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + eps) * p["scale"]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (S, heads, hd): rotate the halves (x1, x2) by position * freq."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def activation(x: jax.Array, conf: Dict[str, Any]) -> jax.Array:
    act = conf["activation_function"]
    if act == "gelu_tanh":
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))
    if act == "relu":
        return jnp.maximum(x, 0.0)
    raise ValueError(f"activation {act!r} is not a gate-less activation")


def layer(w: Weights, x: jax.Array, conf: Dict[str, Any], *,
          lowp: bool = False) -> jax.Array:
    """One block over one sequence: x (S, d) -> (S, d), causal."""
    S = x.shape[0]
    h = norm(w["attn_norm"], x, conf)
    q = mm("sd,dhk->shk", h, w["wq"], lowp) + w["bq"]
    k = mm("sd,dhk->shk", h, w["wk"], lowp) + w["bk"]
    v = mm("sd,dhk->shk", h, w["wv"], lowp) + w["bv"]
    if conf["position_embedding"] == "rope":
        pos = jnp.arange(S)
        q = rope(q, pos, conf["rope_theta"])
        k = rope(k, pos, conf["rope_theta"])
    H, K, hd = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, H // K, axis=1)          # head h reads kv head h // (H/K)
    v = jnp.repeat(v, H // K, axis=1)
    outs = []
    for q0 in range(0, S, ATTN_ROWS):          # causal: rows see keys < q1
        q1 = min(q0 + ATTN_ROWS, S)
        s = mm("qhk,thk->hqt", q[q0:q1], k[:q1], lowp) / math.sqrt(hd)
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        s = jnp.where(causal[None], s, -1e30)
        outs.append(mm("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v[:q1],
                       lowp))
    o = jnp.concatenate(outs, axis=0)
    x = x + mm("qhk,hkd->qd", o, w["wo"], lowp)
    h = norm(w["mlp_norm"], x, conf)
    if gated(conf):
        a = jax.nn.silu(mm("sd,df->sf", h, w["wi"], lowp)) \
            * mm("sd,df->sf", h, w["wg"], lowp)
    else:
        a = activation(mm("sd,df->sf", h, w["wi"], lowp), conf)
    return x + mm("sf,fd->sd", a, w["wo_mlp"], lowp)


def embed(w: Weights, tokens: jax.Array, conf: Dict[str, Any]) -> jax.Array:
    x = w["embed"][tokens].astype(jnp.float32)
    if conf["position_embedding"] == "learned":
        x = x + w["pos"][:tokens.shape[0]]
    return x


def head(w: Weights, x: jax.Array, conf: Dict[str, Any], *,
         lowp: bool = False) -> jax.Array:
    """Final norm and output head: x (S, d) -> logits (S, V)."""
    h = norm(w["final_norm"], x, conf)
    if conf["tie_word_embeddings"]:
        return mm("sd,vd->sv", h, w["embed"], lowp)
    return mm("sd,dv->sv", h, w["lm_head"], lowp)


def layer_slice(w: Weights, i: int) -> Weights:
    return jax.tree.map(lambda a: a[i], w["layers"])


def sequence_loss(w: Weights, tokens: jax.Array, labels: jax.Array,
                  conf: Dict[str, Any], *, lowp: bool = False) -> jax.Array:
    """Summed next-token cross-entropy of one sequence."""
    x = embed(w, tokens, conf)
    for i in range(conf["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda wl, xx: layer(wl, xx, conf, lowp=lowp))(layer_slice(w, i), x)
    logits = head(w, x, conf, lowp=lowp)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


@functools.lru_cache(maxsize=None)
def _batch_grad(conf_json: str, lowp: bool):
    """One jitted program per configuration: the summed loss and gradient
    of a (B, S) batch, a scan over its rows."""
    conf = json.loads(conf_json)

    def row(w, acc, tl):
        loss, g = jax.value_and_grad(
            lambda ww: sequence_loss(ww, tl[0], tl[1], conf, lowp=lowp))(w)
        return jax.tree.map(jnp.add, acc, g), loss

    def batch(w, tokens, labels):
        zero = jax.tree.map(jnp.zeros_like, w)
        grad, losses = jax.lax.scan(lambda acc, tl: row(w, acc, tl), zero,
                                    (tokens, labels))
        n = tokens.shape[0] * tokens.shape[1]
        return jnp.sum(losses) / n, jax.tree.map(lambda g: g / n, grad)

    return jax.jit(batch)


def loss_and_grad(w: Weights, tokens, labels, conf: Dict[str, Any], *,
                  lowp: bool = False):
    """Mean cross-entropy over a (B, S) batch and its gradient, one row
    at a time so that a row's scores are the largest thing held."""
    fn = _batch_grad(json.dumps(conf, sort_keys=True), lowp)
    return fn(w, jnp.asarray(tokens), jnp.asarray(labels))
