"""Roofline share of the FA-2 forward kernel
(``repro.kernels.flash_attention.kernel``) in the training step: the
least time of its required work (from shapes) over its device time.

The kernel is a Pallas call inside ``flash_attention_bhsd``; the trace
names its instruction after that function, and the forward is the call
that returns the bf16 output beside the f32 log-sum-exp."""

from benchmarks.chip.metrics import _attention

LAYER = "kernels (repro.kernels.flash_attention)"
MOVES = "train_tokens_per_s"
PATTERN = r"^%?\S*flash_attention\S* = \(bf16\["


def read(obs):
    return _attention.share(obs, PATTERN, backward=False)
