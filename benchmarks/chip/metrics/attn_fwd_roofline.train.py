"""Roofline share of the FA-2 forward kernel
(``repro.kernels.flash_attention.kernel``) in the training step: the
least time of its required work (from shapes) over its device time.

The kernel is a Pallas call inside ``flash_attention_bhsd``; the trace
names its instruction after that function, and the forward is the one
whose ``op_name`` in the timed step has no ``transpose(``."""

from benchmarks.chip.metrics import _attention

LAYER = "kernels (repro.kernels.flash_attention)"
MOVES = "train_tokens_per_s"
PATTERN = _attention.PATTERN


def read(obs):
    return _attention.share(obs, PATTERN, backward=False)
