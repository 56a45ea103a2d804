"""Model FLOP/s utilization of training: the forward and backward
operations each token requires (the family's ``train_flops_per_token``:
6 N without the input embedding, plus causal attention; nothing
recomputed) times tokens per second of the untraced window, over the
chip's peak."""

from benchmarks.chip import peaks

LAYER = "model step (repro.models)"
MOVES = "train_tokens_per_s"


def read(obs):
    w = obs.get("window", {})
    if "tokens_per_s" not in w or "flops_per_token" not in obs:
        return None
    peak = peaks.lookup(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * obs["flops_per_token"] * w["tokens_per_s"] \
        / (peak * obs.get("chips", 1))
