"""Where the benchmark meets the program under test (``src/repro``).

The one module that knows the program's configuration class and its
parameter layout: it builds the program's ``ModelConfig`` from a
configuration file, and moves leaves between the benchmark's canonical
weight layout (``weights.py``) and the program's tree.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file's keys."""
    from repro.models.config import ModelConfig
    from benchmarks.chip.weights import dims
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


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def to_program(conf: Dict[str, Any], canon: Dict[str, Any], cfg):
    """The program's parameter tree holding the canonical leaves; the
    vocabulary is padded with zero rows to the program's padded size."""
    import jax.numpy as jnp
    from benchmarks.chip.references.decoder import flatten
    flat = flatten(canon)
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


def from_program(conf: Dict[str, Any], tree, vocab: int) -> Dict[str, Any]:
    """Canonical flat {name: leaf} of a program-layout tree (the padded
    vocabulary rows dropped)."""
    out = {}
    for name, path in _pairs(conf):
        x = _get(tree, path)
        if name == "embed":
            x = x[:vocab]
        elif name == "lm_head":
            x = x[:, :vocab]
        out[name] = x
    return out
