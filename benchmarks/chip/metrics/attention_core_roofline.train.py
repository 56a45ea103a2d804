"""Roofline share of the attention core in the training step: the least
time of the causal attention the step requires, forward and backward
(``flops.attention_cost``, once per layer), over the device time of every
op under the program's ``attention_core`` scope
(``repro.models.layers.run_attention``): whatever implements q, k, v ->
out, with its layout transposes and the backward's extra sums."""

from benchmarks.chip import flops
from benchmarks.chip.metrics import _scoped

LAYER = "attention core (repro.models.layers.run_attention)"
MOVES = "train_tokens_per_s"


def read(obs):
    n, job = obs["family"].dims(obs["config"]), obs["traffic"]
    args = (job["batch"], job["seq_len"], n["H"], n["K"], n["hd"])
    fwd = flops.attention_cost(*args)
    bwd = flops.attention_cost(*args, backward=True)
    cost = (n["L"] * (fwd[0] + bwd[0]), n["L"] * (fwd[1] + bwd[1]))
    return _scoped.share(obs, "attention_core", cost)
