"""Roofline share of the FA-2 backward kernels
(``repro.kernels.flash_attention.kernel``: the dq kernel and the dk/dv
kernel) in the training step: the least time of the backward's required
work (twice the forward's products; the recomputed scores do not count)
over the two kernels' summed device time.

In the trace both are instructions named after ``flash_attention_bhsd``
whose ``op_name`` in the timed step lies under ``transpose(``."""

from benchmarks.chip.metrics import _attention

LAYER = "kernels (repro.kernels.flash_attention)"
MOVES = "train_tokens_per_s"
PATTERN = _attention.PATTERN


def read(obs):
    return _attention.share(obs, PATTERN, backward=True)
