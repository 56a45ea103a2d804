"""Shared by the device idle readers: 1 - busy / window over the traced
slice, busy being the union of the intervals in which an operation ran
(``tracing.busy_ns``), averaged over the chips."""


def share(obs):
    s = obs.get("trace_summary")
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
