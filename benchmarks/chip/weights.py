"""The benchmark's own weights, made from the seed.

Both the program under test and the plain reference receive these, so
neither takes weights from the other.  Every leaf of every layer has a key
of its own, derived from the seed, the leaf's name and the layer index, so
that the reference can make one layer at a time and get exactly the values
the program was given for that layer.

Layout (the canonical dictionary ``references/decoder.py`` reads):
``embed`` (V, d), ``pos`` (P, d) where positions are learned, ``lm_head``
(d, V) where the head is untied, ``final_norm``, and ``layers`` holding
each per-layer leaf stacked on a leading axis of ``num_hidden_layers``.
Matrices are normal with standard deviation 1/sqrt(fan-in); norm scales
are 1 + 0.05 N(0, 1); norm and projection biases are 0.05 N(0, 1), so
that every term of the layer equations is exercised.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Tuple[Tuple[int, ...], str, int]     # shape, kind, fan-in


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {"d": d, "H": H, "K": conf.get("num_key_value_heads", H),
            "hd": conf.get("head_dim", d // H),
            "f": conf.get("intermediate_size", conf.get("ffn_dim")),
            "V": conf["vocab_size"], "L": conf["num_hidden_layers"]}


def norm_specs(conf: Dict[str, Any], d: int) -> Dict[str, Spec]:
    out = {"scale": ((d,), "scale", 1)}
    if conf["norm"] == "layernorm":
        out["bias"] = ((d,), "bias", 1)
    return out


def layer_specs(conf: Dict[str, Any]) -> Dict[str, Any]:
    n = dims(conf)
    d, H, K, hd, f = n["d"], n["H"], n["K"], n["hd"], n["f"]
    s: Dict[str, Any] = {
        "attn_norm": norm_specs(conf, d),
        "wq": ((d, H, hd), "matrix", d), "wk": ((d, K, hd), "matrix", d),
        "wv": ((d, K, hd), "matrix", d),
        "bq": ((H, hd), "bias", 1), "bk": ((K, hd), "bias", 1),
        "bv": ((K, hd), "bias", 1),
        "wo": ((H, hd, d), "matrix", H * hd),
        "mlp_norm": norm_specs(conf, d),
        "wi": ((d, f), "matrix", d), "wo_mlp": ((f, d), "matrix", f),
    }
    if conf["activation_function"] == "silu":
        s["wg"] = ((d, f), "matrix", d)
    return s


def top_specs(conf: Dict[str, Any]) -> Dict[str, Any]:
    n = dims(conf)
    d = n["d"]
    s: Dict[str, Any] = {"embed": ((n["V"], d), "matrix", d),
                         "final_norm": norm_specs(conf, d)}
    if conf["position_embedding"] == "learned":
        s["pos"] = ((conf["max_position_embeddings"], d), "matrix", d)
    if not conf["tie_word_embeddings"]:
        s["lm_head"] = ((d, n["V"]), "matrix", d)
    return s


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number below 2**64 (seeds pass 32 bits)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))


def _make(spec: Spec, key: jax.Array, dtype) -> jax.Array:
    shape, kind, fan_in = spec
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        x = z / np.sqrt(fan_in)
    elif kind == "scale":
        x = 1.0 + 0.05 * z
    else:
        x = 0.05 * z
    return x.astype(dtype)


def _tree(specs: Dict[str, Any], key: jax.Array, dtype, prefix: str = ""):
    out = {}
    for k, v in specs.items():
        name = prefix + k
        out[k] = _tree(v, key, dtype, name + ".") if isinstance(v, dict) \
            else _make(v, _leaf_key(key, name), dtype)
    return out


def layer_weights(conf: Dict[str, Any], key: jax.Array, i, dtype):
    """Layer ``i``'s leaves; ``key`` is :func:`seed_key` of the seed."""
    lkey = jax.random.fold_in(_leaf_key(key, "layers"), i)
    return _tree(layer_specs(conf), lkey, dtype)


def top_weights(conf: Dict[str, Any], key: jax.Array, dtype):
    return _tree(top_specs(conf), _leaf_key(key, "top"), dtype)


def all_weights(conf: Dict[str, Any], key: jax.Array, dtype):
    """Every leaf, layers stacked on a leading axis.  Traced inside one
    jit with ``key`` as its argument, so a new seed compiles nothing."""
    layers = jax.vmap(lambda i: layer_weights(conf, key, i, dtype))(
        jnp.arange(conf["num_hidden_layers"]))
    return dict(top_weights(conf, key, dtype), layers=layers)
