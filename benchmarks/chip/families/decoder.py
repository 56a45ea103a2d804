"""The dense decoder family: OPT's and Qwen2's block, as the program's
``arch_type="dense"`` runs it (``src/repro``), and everything the
benchmark needs to know of it.

A family module is where the benchmark meets the program's model.  A
configuration file names its family (``"family"``), and the harness loads
``families/<family>.py``; the runners and readers reach the model only
through it.  Every family gives:

* ``SCOPES``: the ``jax.named_scope`` names its program's step uses
  (``scopes.py`` splits the step's device time by them);
* ``model_config(conf)``: the program's ``ModelConfig``;
* ``weights(conf, key, dtype)``: the canonical seeded weights, a
  dictionary made by ``seeded.tree`` from ``key`` (``seeded.seed_key`` of
  the seed), traceable inside one jit with ``key`` as its argument;
* ``to_program(conf, canon, cfg)`` and ``from_program(conf, tree, cfg)``:
  the canonical weights in the program's tree, and back as a flat
  {dotted name: leaf};
* ``loss_and_grad(w, tokens, labels, conf, *, lowp)``: the float32
  reference's mean loss over a batch and its gradient (float8 products
  with ``lowp``: the control);
* ``train_flops_per_token(conf, seq)``: forward and backward operations
  per token (``flops.py``'s conventions);
* ``dims(conf)``: the sizes the readers count from.

This family's canonical dictionary: ``embed`` (V, d), ``pos`` (P, d)
where positions are learned, ``lm_head`` (d, V) where the head is untied,
``final_norm``, and ``layers`` holding each per-layer leaf stacked on a
leading axis of ``num_hidden_layers``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.chip import seeded
from benchmarks.chip.references.decoder import loss_and_grad  # noqa: F401

SCOPES = ("embed", "attention", "attention_core", "mlp", "lm_head",
          "optimizer")


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {"d": d, "H": H, "K": conf.get("num_key_value_heads", H),
            "hd": conf.get("head_dim", d // H),
            "f": conf.get("intermediate_size", conf.get("ffn_dim")),
            "V": conf["vocab_size"], "L": conf["num_hidden_layers"]}


# ------------------------------------------------------------ the program

def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file's keys."""
    from repro.models.config import ModelConfig
    n = dims(conf)
    act = conf["activation_function"]
    learned = conf["position_embedding"] == "learned"
    cfg = ModelConfig(
        name=conf["name"], arch_type="dense", num_layers=n["L"],
        d_model=n["d"], num_heads=n["H"], num_kv_heads=n["K"],
        head_dim=n["hd"], d_ff=n["f"], vocab_size=n["V"],
        qkv_bias=conf["qkv_bias"],
        pos_embedding="learned" if learned else "rope",
        rope_theta=conf.get("rope_theta", 10_000.0),
        max_target_positions=conf["max_position_embeddings"] if learned
        else 0,
        mlp_activation={"silu": "silu", "gelu_tanh": "gelu"}[act],
        norm_type=conf["norm"], norm_eps=conf["layer_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        param_dtype=conf["param_dtype"], compute_dtype=conf["compute_dtype"],
        source=conf["source"])
    cfg.validate()
    return cfg


def _pairs(conf: Dict[str, Any]) -> List[Tuple[str, Tuple[str, ...]]]:
    """(canonical name, program path) for every leaf."""
    att, mlp = ("decoder", "g0", "s0_attn"), ("decoder", "g0", "s1_mlp")
    norms = ["scale"] + (["bias"] if conf["norm"] == "layernorm" else [])
    out = [("embed", ("embed", "tok"))]
    if conf["position_embedding"] == "learned":
        out.append(("pos", ("embed", "pos")))
    if not conf["tie_word_embeddings"]:
        out.append(("lm_head", ("lm_head",)))
    out += [(f"final_norm.{k}", ("final_norm", k)) for k in norms]
    out += [(f"layers.attn_norm.{k}", att + ("norm", k)) for k in norms]
    out += [(f"layers.{k}", att + (k,))
            for k in ("wq", "wk", "wv", "bq", "bk", "bv", "wo")]
    out += [(f"layers.mlp_norm.{k}", mlp + ("norm", k)) for k in norms]
    out += [("layers.wi", mlp + ("wi",)), ("layers.wo_mlp", mlp + ("wo",))]
    if conf["activation_function"] == "silu":
        out.append(("layers.wg", mlp + ("wg",)))
    return out


def to_program(conf: Dict[str, Any], canon: Dict[str, Any], cfg):
    """The program's parameter tree holding the canonical leaves; the
    vocabulary is padded with zero rows to the program's padded size."""
    flat = seeded.flatten(canon)
    pad = cfg.padded_vocab_size - cfg.vocab_size
    tree: Dict[str, Any] = {}
    for name, path in _pairs(conf):
        x = flat[name]
        if name == "embed" and pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        elif name == "lm_head" and pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x
    return tree


def from_program(conf: Dict[str, Any], tree, cfg) -> Dict[str, Any]:
    """Canonical flat {name: leaf} of a program-layout tree (the padded
    vocabulary rows dropped)."""
    out = {}
    for name, path in _pairs(conf):
        x = seeded.lookup(tree, ".".join(path))
        if name == "embed":
            x = x[:cfg.vocab_size]
        elif name == "lm_head":
            x = x[:, :cfg.vocab_size]
        out[name] = x
    return out


# ------------------------------------------------------ the seeded weights

def norm_specs(conf: Dict[str, Any], d: int) -> Dict[str, seeded.Spec]:
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


def layer_weights(conf: Dict[str, Any], key, i, dtype):
    """Layer ``i``'s leaves; ``key`` is ``seeded.seed_key`` of the seed."""
    lkey = jax.random.fold_in(seeded.leaf_key(key, "layers"), i)
    return seeded.tree(layer_specs(conf), lkey, dtype)


def weights(conf: Dict[str, Any], key, dtype):
    """Every leaf, layers stacked on a leading axis.  Traced inside one
    jit with ``key`` as its argument, so a new seed compiles nothing."""
    layers = jax.vmap(lambda i: layer_weights(conf, key, i, dtype))(
        jnp.arange(conf["num_hidden_layers"]))
    top = seeded.tree(top_specs(conf), seeded.leaf_key(key, "top"), dtype)
    return dict(top, layers=layers)


# ------------------------------------------------------------ the counts

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
