"""Share of the traced slice in which no operation ran on the chip."""

from benchmarks.chip.metrics import _idle

LAYER = "device"
MOVES = "train_tokens_per_s"


def read(obs):
    return _idle.share(obs)
