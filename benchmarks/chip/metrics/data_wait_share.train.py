"""Share of the training window the host spent drawing and staging the
next batch (``next(batches)`` and ``jax.device_put``, the data layer,
``repro.data.pipeline``), by the benchmark's own host-clock span."""

LAYER = "data (repro.data.pipeline)"
MOVES = "train_tokens_per_s"


def read(obs):
    w = obs.get("window", {})
    if "data_s" not in w:
        return None
    return 100.0 * w["data_s"] / w["window_s"]
