"""Drives the program's training step: the trainer's own jitted step
(``repro.train.trainer.make_jit_train_step``: forward, FA-2 backward,
AdamW, donation) fed by the program's data pipeline
(``repro.data.pipeline.make_batch_fn``) in the trainer's order: dispatch a
step, then draw and stage the next batch while it runs, with as many
steps in flight as the trainer's loop allows before it syncs.

One object, the compiled step with its state, is built in set-up and
driven through the first steps, whose losses, first gradient (read from
AdamW's first moment after one step) and change of the parameters after
``check_steps`` steps are kept; the same object then runs the measured
window.  After the window the float32 reference replays those steps from
the same weights and batches, and ``compare.train_readings`` decides
``correct``.

Everything that depends on the model (its ``ModelConfig``, weights,
layout, reference, operation count and scopes) comes from the cell's
family (``cell.family``), so any family's configuration trains here.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks.chip import compare, harness, tracing
from benchmarks.chip.references import adamw as A
from benchmarks.chip.seeded import flatten, seed_key

TRACE_S = 3.0               # length of the traced slice after the window
STEP_MODULE = "jit_train_step"   # the step's modules, as the trace names them


def _opt_config(opt: Dict[str, Any]):
    from repro.optim import adamw
    return adamw.OptConfig(
        learning_rate=opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
        eps=opt["eps"], weight_decay=opt["weight_decay"],
        clip_norm=opt["clip_norm"], warmup_steps=opt["warmup_steps"],
        decay_steps=opt["decay_steps"], min_lr_ratio=opt["min_lr_ratio"])


def _planted(step, fault: Optional[str]):
    """The step with a fault planted underneath it (tests only)."""
    if fault is None:
        return step
    if fault == "state_unchanged":
        def unchanged(p, o, b):
            _, _, m = step(p, o, b)
            return p, o, m
        return unchanged
    if fault == "half_batch":
        return lambda p, o, b: step(
            p, o, {k: v[:v.shape[0] // 2] for k, v in b.items()})
    raise ValueError(f"unknown fault {fault!r}")


class Trainer:
    """The program's step, its state and its feed, built from the seed."""

    def __init__(self, cell: harness.Cell, seed: int,
                 fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp
        from repro.data.pipeline import make_batch_fn
        from repro.optim import adamw
        from repro.train.trainer import TrainerConfig, make_jit_train_step
        self.conf, self.job, self.family = cell.config, cell.traffic, \
            cell.family
        self.cfg = self.family.model_config(self.conf)
        self.B, self.S = self.job["batch"], self.job["seq_len"]
        oc = _opt_config(self.job["optimizer"])
        tc = TrainerConfig(batch=self.B, seq_len=self.S,
                           attn_impl=self.job["attn_impl"],
                           donate=fault is None, seed=seed)
        self.key = seed_key(seed)
        dtype = jnp.dtype(self.conf["param_dtype"])
        conf, cfg, fam = self.conf, self.cfg, self.family
        # one jitted call makes every weight on the device from the seed
        self.params = jax.jit(lambda k: fam.to_program(
            conf, fam.weights(conf, k, dtype), cfg))(self.key)
        self.opt_state = jax.jit(
            lambda p: adamw.init_opt_state(p, oc))(self.params)
        self.step = _planted(make_jit_train_step(cfg, tc, oc), fault)
        self.data = make_batch_fn(cfg, self.B, self.S, seed)
        self.fed: List[Dict[str, Any]] = [next(self.data)]
        self.batch = jax.device_put(self.fed[0])
        self.b1 = oc.b1
        # the trainer's own loop lets up to ``log_every`` steps run ahead
        # of the host before it drains their metrics; the window keeps as
        # many in flight, so that a pause of the host shorter than that
        # many steps leaves the chip busy
        self.in_flight = tc.log_every

    def advance(self):
        """One step through the timed path's call and feed; returns the
        step's metrics, the batch fed, and the host seconds spent
        dispatching it and staging the next batch."""
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.params, self.opt_state, m = self.step(
                self.params, self.opt_state, self.batch)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.data"):
            host = next(self.data)
            self.batch = jax.device_put(host)
        return m, host, t1 - t, time.perf_counter() - t1

    def first_steps(self, n: int) -> Dict[str, Any]:
        """Steps 1..n of set-up, and what the comparison keeps of them."""
        import jax
        import numpy as np
        losses, grad1 = [], None
        for i in range(n):
            m, host, _, _ = self.advance()
            self.fed.append(host)
            losses.append(m["loss"])
            if i == 0:     # AdamW's first moment after one step: (1-b1) g
                mu = self.family.from_program(self.conf,
                                              self.opt_state["mu"], self.cfg)
                grad1 = compare.norms(
                    {k: np.asarray(v, np.float64) / (1 - self.b1)
                     for k, v in jax.device_get(mu).items()})
        after = self.family.from_program(self.conf, self.params, self.cfg)
        return {"losses": [float(x) for x in jax.device_get(losses)],
                "grad_norms": grad1,
                "params_after": jax.device_get(after),
                "batches": self.fed[:n]}

    def window(self, seconds: float) -> Dict[str, Any]:
        """Steps for ``seconds``: tokens of all completed steps over the
        time from the first dispatch to the last step's completion.  Each
        step's host seconds (dispatch, data, wait for the step
        ``in_flight`` back) and the garbage collector's pauses are kept,
        to find a stall."""
        import jax
        pending: deque = deque()
        steps: List[Tuple[int, float, float, float, float]] = []
        pauses = _GcClock()
        gc.callbacks.append(pauses)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                while True:
                    start = time.perf_counter() - t0
                    m, _, dispatch_s, data_s = self.advance()
                    pending.append(m["loss"])
                    t = time.perf_counter()
                    if len(pending) > self.in_flight:
                        with jax.profiler.TraceAnnotation("bench.sync"):
                            pending.popleft().block_until_ready()
                    steps.append((len(steps), start, dispatch_s, data_s,
                                  time.perf_counter() - t))
                    if time.perf_counter() - t0 >= seconds:
                        break
                jax.block_until_ready(m["loss"])
            wall = time.perf_counter() - t0
        finally:
            gc.callbacks.remove(pauses)
        n = len(steps)
        return {"steps": n, "window_s": wall,
                "data_s": sum(s[3] for s in steps),
                "tokens_per_s": n * self.B * self.S / wall,
                "slowest": slowest(steps), "gc_n": pauses.n,
                "gc_s": pauses.s}


class _GcClock:
    """Counts the garbage collector's passes and the seconds they take
    (a ``gc.callbacks`` entry)."""

    def __init__(self):
        self.n, self.s, self._t = 0, 0.0, 0.0

    def __call__(self, phase: str, info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.n += 1
            self.s += time.perf_counter() - self._t


def slowest(steps: Sequence[Tuple[int, float, float, float, float]],
            top: int = 3) -> List[Tuple[int, float, float, float, float]]:
    """The ``top`` steps that held the host longest, as (index, start s,
    dispatch s, data s, wait s)."""
    return sorted(steps, key=lambda s: -(s[2] + s[3] + s[4]))[:top]


def reference(cell: harness.Cell, seed: int, batches, *,
              lowp: bool = False) -> Dict[str, Any]:
    """The float32 reference (float8 products with ``lowp``) replaying
    the first steps from the same weights and batches."""
    import jax
    import jax.numpy as jnp
    conf, fam, opt = cell.config, cell.family, cell.traffic["optimizer"]
    w0 = jax.jit(lambda k: fam.weights(conf, k, jnp.float32))(
        seed_key(seed))
    w = w0
    m = v = jax.jit(lambda x: jax.tree.map(jnp.zeros_like, x))(w0)
    losses, grad1, grad_s = [], None, 0.0
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            tg = time.perf_counter()
            loss, g = fam.loss_and_grad(w, b["tokens"], b["labels"], conf,
                                        lowp=lowp)
            losses.append(float(loss))
            grad_s += time.perf_counter() - tg
            w, m, v, gc_ = A.adamw_step(w, g, m, v, i + 1, opt,
                                        opt["no_decay"])
            if i == 0:
                grad1 = compare.norms(flatten(gc_))
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        flatten(w), flatten(w0))
    return {"losses": losses, "grad_norms": grad1,
            "update_norms": compare.norms(delta), "w0": flatten(w0),
            "grad_s": grad_s}


def program_update_norms(first: Dict[str, Any], w0) -> Dict[str, float]:
    import numpy as np
    return compare.norms({k: np.asarray(first["params_after"][k], np.float64)
                          - np.asarray(w0[k], np.float64)
                          for k in first["params_after"]})


def compiled_text(step, args):
    """The optimized HLO text of ``step`` on the shapes, types and
    placements of ``args``, as a call that compiles only when a reader
    asks for it: the jitted step the window ran, so the compiler gives
    the program that ran.  Lowered from shapes, it is a cache entry of its
    own: the first traced run in a checkout compiles it once (outside
    the window and set-up), later runs read it from the persistent
    cache."""
    import jax
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding,
                                       weak_type=x.weak_type), args)
    return lambda: step.lower(*shapes).compile().as_text()


def calibrate(cell: harness.Cell, seeds: Sequence[int],
              control: Sequence[int], faults: Sequence[int],
              devices) -> Iterator[Dict[str, Any]]:
    """Readings that the limits are set from, one dict per reading: the
    program on every seed of ``seeds``, the program with half of each
    batch left out on every other seed of ``faults``, and the control
    (the reference with float8 products in the program's place) on every
    seed of ``control``.  The readings come from set-up's first steps, so
    no window runs."""
    for seed in list(seeds) + [s for s in faults if s not in seeds]:
        fault = None if seed in seeds else "half_batch"
        tr = Trainer(cell, seed, fault)
        first = tr.first_steps(cell.traffic["check_steps"])
        del tr
        gc.collect()
        ref = reference(cell, seed, first["batches"])
        got = compare.train_readings(
            first["losses"], first["grad_norms"],
            program_update_norms(first, ref["w0"]), ref)
        yield {"seed": seed, "kind": fault or "program", **got}
        if seed in control:
            low = reference(cell, seed, first["batches"], lowp=True)
            got = compare.train_readings(low["losses"], low["grad_norms"],
                                         low["update_norms"], ref)
            yield {"seed": seed, "kind": "control", **got}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        workdir: Path, devices, limits: Dict[str, float],
        fault: Optional[str] = None) -> harness.Outcome:
    import jax
    t_start = time.perf_counter()
    tr = Trainer(cell, seed, fault)
    first = tr.first_steps(cell.traffic["check_steps"])
    jax.block_until_ready(tr.params)
    setup_s = time.perf_counter() - t_start
    print(f"[train] set-up {setup_s:.2f}s; first losses {first['losses']}",
          flush=True)
    win = tr.window(seconds)
    print(f"[train] window {win['window_s']:.3f}s, {win['steps']} steps, "
          f"{win['tokens_per_s']:.1f} tokens/s, data {win['data_s']:.3f}s; "
          f"slowest steps (index, start, dispatch, data, wait s) "
          f"{[tuple(round(x, 4) for x in st) for st in win['slowest']]}; "
          f"gc {win['gc_n']} passes {win['gc_s']:.4f}s",
          flush=True)
    obs: Dict[str, Any] = {"config": cell.config, "traffic": cell.traffic,
                           "family": cell.family,
                           "device_kind": devices[0].device_kind,
                           "chips": len(devices),
                           "window": win,
                           "flops_per_token":
                               cell.family.train_flops_per_token(
                                   cell.config, tr.S),
                           "step_module": STEP_MODULE}
    summary = None
    if trace:
        with tracing.capture(workdir / "trace"):
            with jax.profiler.TraceAnnotation("bench.traced"):
                tr.window(TRACE_S)
        obs["trace"] = tracing.load(workdir / "trace")
        summary = tracing.summarize(obs["trace"], "bench.traced")
        obs["trace_summary"] = summary
        obs["step_hlo"] = compiled_text(tr.step, (tr.params, tr.opt_state,
                                                  tr.batch))
    peak = harness.memory_peak_bytes(devices)
    print(f"[train] memory_stats {[d.memory_stats() for d in devices]}",
          flush=True)
    del tr
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference(cell, seed, first["batches"])
    got = compare.train_readings(first["losses"], first["grad_norms"],
                                 program_update_norms(first, ref["w0"]), ref)
    checks = [harness.check(k, got[k], limits[k]) for k in limits]
    print(f"[train] readings without a limit: "
          f"{ {k: v for k, v in got.items() if k not in limits} }",
          flush=True)
    print(f"[train] reference losses {ref['losses']} in "
          f"{time.perf_counter() - t_ref:.1f}s (gradients "
          f"{ref['grad_s']:.1f}s)", flush=True)
    return harness.Outcome(
        correct=all(c["ok"] for c in checks), attempted=win["steps"],
        failed=0,
        end_to_end={"train_tokens_per_s": win["tokens_per_s"],
                    "setup_s": setup_s},
        checks=checks, memory_peak_bytes=peak, obs=obs,
        breakdown=summary["breakdown"] if summary else None)
