"""Shared by the scope readers: a layer's least time over the device time
the traced steps spent under its scope (``scopes.read``), in percent."""

from benchmarks.chip import flops, peaks, scopes


def share(obs, scope: str, cost) -> float | None:
    """``cost`` is the (operations, bytes) the scope's work requires per
    step."""
    s = scopes.read(obs)
    if s is None or scopes.scope_s(s, scope) <= 0:
        return None
    need = flops.roofline_s(*cost, peaks.lookup(obs["device_kind"]))
    return 100.0 * need / scopes.scope_s(s, scope)
