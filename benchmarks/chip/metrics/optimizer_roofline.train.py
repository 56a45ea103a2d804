"""Roofline share of the optimizer in the training step: the least time
of the bytes AdamW with global-norm clipping must move over every
float32 parameter (``layer_costs.adamw_cost``), over the device time of
the ops under the program's ``optimizer`` scope (``repro.train.step``
around ``repro.optim.adamw.apply_updates``)."""

from benchmarks.chip import layer_costs
from benchmarks.chip.metrics import _scoped

LAYER = "optimizer (repro.optim.adamw)"
MOVES = "train_tokens_per_s"


def read(obs):
    cost = layer_costs.adamw_cost(
        layer_costs.param_count(obs["family"], obs["config"]))
    return _scoped.share(obs, "optimizer", cost)
