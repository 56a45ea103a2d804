"""Roofline share of the MLP sublayers in the training step: the least
time of their matrix products, forward and backward, in every layer
(``layer_costs.mlp_cost``), over the device time of the ops under the
program's ``mlp`` scope (``repro.models.layers.mlp_forward``: the norm,
both products, the activation and the residual)."""

from benchmarks.chip import layer_costs
from benchmarks.chip.metrics import _scoped

LAYER = "model step (repro.models)"
MOVES = "train_tokens_per_s"


def read(obs):
    conf, job = obs["config"], obs["traffic"]
    n = obs["family"].dims(conf)
    fl, by = layer_costs.mlp_cost(
        job["batch"] * job["seq_len"], n["d"], n["f"],
        gated=conf["activation_function"] == "silu")
    return _scoped.share(obs, "mlp", (n["L"] * fl, n["L"] * by))
