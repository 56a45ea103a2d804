"""Share of the training step's device time spent in ops under none of
the program's named scopes (the family's ``SCOPES``): the work no
per-layer metric can see (the final norm, the layer scan's plumbing)."""

from benchmarks.chip import scopes

LAYER = "model step (repro.models)"
MOVES = "train_tokens_per_s"


def read(obs):
    s = scopes.read(obs)
    if s is None:
        return None
    return 100.0 * s["unscoped"] / s["total"]
