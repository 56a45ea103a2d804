"""Roofline share of the output head in the training step: the least
time of its product over the vocabulary, forward and backward
(``layer_costs.lm_head_cost``), over the device time of the ops under the
program's ``lm_head`` scope (``repro.models.model.forward_train``: the
logits and the cross entropy)."""

from benchmarks.chip import layer_costs
from benchmarks.chip.metrics import _scoped

LAYER = "model step (repro.models)"
MOVES = "train_tokens_per_s"


def read(obs):
    n, job = obs["family"].dims(obs["config"]), obs["traffic"]
    cost = layer_costs.lm_head_cost(job["batch"] * job["seq_len"], n["d"],
                                    n["V"])
    return _scoped.share(obs, "lm_head", cost)
