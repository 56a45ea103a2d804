"""Compilations of the training step after its first: traces plus
backend compiles of ``train_step`` recorded by the program's compile
counter (``repro.obs.compiles``, which ``make_jit_train_step`` installs),
less the one trace and the one compile of set-up.  The window calls
nothing else that can compile (its batches are numpy, staged with
``device_put``), so any count above 0 is a compile inside the window or
the traced slice.  The compile the scope readers make to map the trace
(``scopes.read``) is left out."""

LAYER = "model step (repro.models)"
MOVES = "train_tokens_per_s"
STEP = ("train_step", "jit(train_step)")


def read(obs):
    try:
        from repro.obs import compiles
    except ImportError:            # a program without the counter
        return None
    if not compiles.installed():
        return None
    lo, hi = obs.get("scope_map_span_s", (float("inf"), float("inf")))
    seen = [e for e in compiles.events()
            if e.fun_name in STEP and not lo <= e.start_s <= hi]
    traces = sum(e.kind == "trace" for e in seen)
    builds = sum(e.kind == "compile" for e in seen)
    if not traces or not builds:
        return None
    return float((traces - 1) + (builds - 1))
