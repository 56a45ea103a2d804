"""DT-FM execution path: GPipe pipeline parallelism via shard_map+ppermute.

The paper's Table 2 method [98] combines data parallelism with pipeline
parallelism across edge devices.  This module runs it for real on a JAX
mesh with a ``stage`` axis:

* the decoder layer stack (uniform ``(attn, mlp)`` groups — the OPT family
  the paper trains) is split into S contiguous stages, parameters sharded
  over ``stage`` on the stacked layer axis,
* stage boundaries may be **non-uniform** (a
  :class:`~repro.core.placement.PlacementSpec` balancing heterogeneous
  devices): every stage is padded to the longest stage's layer count and
  the phantom scan steps are masked out, so a 3-stage split of an
  8-layer model runs as (3, 3, 2) real layers on a (3, 3, 3) scan,
* inside ``shard_map`` each tick runs the local stage and rotates
  activations with ``jax.lax.ppermute`` (the GPipe systolic schedule:
  mb + S - 1 ticks, bubble (S-1)/(mb+S-1)),
* embedding / lm-head / loss run outside the pipelined region (replicated),
* autodiff goes straight through ``ppermute`` — the backward pipeline is
  derived, not hand-scheduled.

Combined with the ``data`` mesh axis this is exactly DT-FM's hybrid
data+pipeline layout, executable on any device count (CPU tests use
``--xla_force_host_platform_device_count``).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import params as PM
from repro.models.blocks import _sublayer_train
from repro.models.config import ModelConfig
from repro.models.model import cross_entropy, embed_tokens, lm_logits
from repro.models.layers import norm

PyTree = Any

# stage boundaries: an int S (uniform split), an explicit [0,...,L] boundary
# list, or anything with a ``.boundaries`` attribute (a PlacementSpec)
Boundaries = Union[int, Sequence[int]]


def resolve_boundaries(cfg: ModelConfig, stages: Boundaries) -> List[int]:
    """Normalize to an explicit boundary list [0, ..., num_layers]."""
    if hasattr(stages, "boundaries"):            # PlacementSpec duck-type
        stages = stages.boundaries
    L = cfg.num_layers
    if isinstance(stages, int):
        if L % stages != 0:
            raise ValueError(
                f"{L} layers do not split uniformly into {stages} stages; "
                "pass explicit boundaries (e.g. a PlacementSpec's)")
        step = L // stages
        return list(range(0, L + 1, step))
    bounds = list(stages)
    if bounds[0] != 0 or bounds[-1] != L or bounds != sorted(bounds) \
            or len(set(bounds)) != len(bounds):
        raise ValueError(
            f"boundaries {bounds} must strictly ascend from 0 to {L}")
    return bounds


def stage_slices(bounds: Sequence[int]) -> List[Tuple[int, int]]:
    """Boundary list -> per-stage [start, stop) layer spans.

    This is the ONE place stage boundary math lives: the pipeline executor
    pads/masks from it and :mod:`repro.checkpoint` shards/reshards
    checkpoints with it, so a checkpoint written under one placement
    re-slices exactly onto the stages another placement executes.
    """
    return list(zip(bounds[:-1], bounds[1:]))


def stage_counts(bounds: Sequence[int]) -> List[int]:
    """Per-stage layer counts for a boundary list."""
    return [b - a for a, b in zip(bounds[:-1], bounds[1:])]


_stage_counts = stage_counts          # internal alias (pre-export name)


def stage_layer_mask(cfg: ModelConfig, stages: Boundaries) -> jax.Array:
    """(S, Lmax) bool: True where a padded scan slot holds a real layer."""
    counts = _stage_counts(resolve_boundaries(cfg, stages))
    lmax = max(counts)
    return jnp.arange(lmax)[None, :] < jnp.asarray(counts)[:, None]


def _stage_forward(cfg: ModelConfig, stage_params: PyTree, x: jax.Array,
                   positions: jax.Array,
                   mask: Optional[jax.Array] = None) -> jax.Array:
    """Run this device's layer slice.  stage_params leaves: (Lmax, ...);
    ``mask`` (Lmax,) skips the zero-padded slots of short stages."""
    ctx = {"positions": positions, "causal": True, "attn_impl": "chunked"}

    def run(h, p_unit):
        for j, kind in enumerate(("attn", "mlp")):
            h, _ = _sublayer_train(kind, p_unit[f"s{j}_{kind}"], h,
                                   jnp.zeros((), jnp.float32), cfg, ctx)
        return h

    if mask is None:
        def body(h, p_unit):
            return run(h, p_unit), None
        h, _ = jax.lax.scan(body, x, stage_params)
    else:
        def body(h, xs):
            p_unit, m = xs
            return jnp.where(m, run(h, p_unit), h), None
        h, _ = jax.lax.scan(body, x, (stage_params, mask))
    return h


def stack_for_stages(cfg: ModelConfig, params: PyTree, stages: Boundaries
                     ) -> PyTree:
    """Reshape decoder stack leaves (L, ...) -> (S, Lmax, ...).

    Uniform splits are a pure reshape; non-uniform boundaries slice each
    stage's layers and zero-pad to the longest stage (the executor masks
    the padding, and zero params receive zero grads, so padded slots stay
    exactly zero through training).
    """
    groups = PM.decoder_groups(cfg)
    assert len(groups) == 1 and groups[0].sublayers == ("attn", "mlp"), \
        "pipeline path supports uniform dense decoders (OPT family)"
    bounds = resolve_boundaries(cfg, stages)
    counts = _stage_counts(bounds)
    S, lmax = len(counts), max(counts)
    L = cfg.num_layers

    if L == S * lmax:                 # uniform: pure reshape
        def reshape(leaf):
            return leaf.reshape((S, lmax) + leaf.shape[1:])
        return jax.tree.map(reshape, params["decoder"]["g0"])

    def slice_pad(leaf):
        parts = []
        for a, b in zip(bounds[:-1], bounds[1:]):
            pad = [(0, lmax - (b - a))] + [(0, 0)] * (leaf.ndim - 1)
            parts.append(jnp.pad(leaf[a:b], pad))
        return jnp.stack(parts)
    return jax.tree.map(slice_pad, params["decoder"]["g0"])


def unstack_stages(cfg: ModelConfig, staged: PyTree,
                   stages: Optional[Boundaries] = None) -> PyTree:
    """Invert :func:`stack_for_stages` (drops non-uniform padding)."""
    L = cfg.num_layers

    if stages is None:                # legacy uniform round-trip
        def reshape(leaf):
            return leaf.reshape((L,) + leaf.shape[2:])
        return jax.tree.map(reshape, staged)

    counts = _stage_counts(resolve_boundaries(cfg, stages))

    def gather(leaf):
        return jnp.concatenate(
            [leaf[i, :c] for i, c in enumerate(counts)], axis=0)
    return jax.tree.map(gather, staged)


def make_pipeline_loss(cfg: ModelConfig, mesh: Mesh, *,
                       num_microbatches: int,
                       boundaries: Optional[Boundaries] = None) -> Callable:
    """loss(params, staged_layers, batch) with the stage axis pipelined.

    ``boundaries`` (a boundary list or PlacementSpec) enables non-uniform
    stage splits; ``None`` keeps the uniform L/S split.
    """
    S = mesh.shape["stage"]
    MB = num_microbatches
    perm = [(i, (i + 1) % S) for i in range(S)]
    bounds = resolve_boundaries(cfg, boundaries if boundaries is not None
                                else S)
    if len(bounds) - 1 != S:
        raise ValueError(
            f"boundaries {bounds} define {len(bounds) - 1} stages but the "
            f"mesh 'stage' axis has {S}")
    uniform = cfg.num_layers == S * max(_stage_counts(bounds))
    mask_all = None if uniform else stage_layer_mask(cfg, bounds)

    def pipelined(staged, mb_embeds, positions, mask):
        """Inside shard_map: staged (1, Lmax, ...) local; mb_embeds
        (MB, mbsz, T, d) replicated; returns (MB, mbsz, T, d) outputs."""
        local = jax.tree.map(lambda l: l[0], staged)
        local_mask = None if mask is None else mask[0]
        stage_id = jax.lax.axis_index("stage")
        mbsz, T, d = mb_embeds.shape[1:]

        def tick(carry, t):
            state, outs = carry
            # stage 0 injects microbatch t (while t < MB)
            inject = mb_embeds[jnp.minimum(t, MB - 1)]
            x = jnp.where(stage_id == 0, inject, state)
            y = _stage_forward(cfg, local, x, positions, local_mask)
            # last stage emits finished microbatch t-(S-1)
            done_idx = t - (S - 1)
            is_done = jnp.logical_and(stage_id == S - 1, done_idx >= 0)
            outs = jax.lax.cond(
                is_done,
                lambda o: o.at[jnp.maximum(done_idx, 0)].set(y),
                lambda o: o, outs)
            state = jax.lax.ppermute(y, "stage", perm)
            return (state, outs), None

        state0 = jnp.zeros((mbsz, T, d), mb_embeds.dtype)
        outs0 = jnp.zeros_like(mb_embeds)
        (_, outs), _ = jax.lax.scan(tick, (state0, outs0),
                                    jnp.arange(MB + S - 1))
        return outs[None]           # stacked over stage; stage S-1 is real

    # check_vma=False: the stage body is the ordinary model code, whose
    # scans (chunked attention, the layer scan) start their carries from
    # zeros that do not vary over "stage" while the stage's activations do
    if mask_all is None:
        sm3 = jax.shard_map(lambda s, e, p: pipelined(s, e, p, None),
                            mesh=mesh, in_specs=(P("stage"), P(), P()),
                            out_specs=P("stage"), check_vma=False)
        sm = lambda s, e, p, _m: sm3(s, e, p)          # noqa: E731
    else:
        sm = jax.shard_map(pipelined, mesh=mesh,
                           in_specs=(P("stage"), P(), P(), P("stage")),
                           out_specs=P("stage"), check_vma=False)

    def loss_fn(params, staged, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        B, T = tokens.shape
        assert B % MB == 0
        x = embed_tokens(params, cfg, tokens)
        if "pos" in params["embed"]:
            x = x + params["embed"]["pos"][:T].astype(x.dtype)[None]
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                                     (B // MB, T))
        mb_embeds = x.reshape(MB, B // MB, T, -1)
        outs = sm(staged, mb_embeds, positions, mask_all)  # (S, MB, mbsz, T, d)
        h = outs[S - 1].reshape(B, T, -1)              # last stage's output
        h = norm(params["final_norm"], h, cfg)
        logits = lm_logits(params, cfg, h)
        loss, _ = cross_entropy(logits, labels)
        return loss

    return loss_fn


def pipeline_train_step(cfg: ModelConfig, mesh: Mesh, opt_cfg, *,
                        num_microbatches: int = 4,
                        boundaries: Optional[Boundaries] = None
                        ) -> Tuple[Callable, Callable]:
    """Returns (init_fn, step_fn) for pipelined training on ``mesh``."""
    from repro.optim import adamw
    loss_fn = make_pipeline_loss(cfg, mesh, num_microbatches=num_microbatches,
                                 boundaries=boundaries)
    S = mesh.shape["stage"]
    stages: Boundaries = boundaries if boundaries is not None else S

    def init_fn(rng):
        params = PM.init_params(cfg, rng)
        staged = stack_for_stages(cfg, params, stages)
        staged = jax.device_put(staged, NamedSharding(mesh, P("stage")))
        rest = dict(params)
        del rest["decoder"]
        # embedding / head / final norm run outside the pipelined region
        # on every stage: replicate them over the mesh rather than leave
        # them on the default device
        rest = jax.device_put(rest, NamedSharding(mesh, P()))
        opt = adamw.init_opt_state({"rest": rest, "staged": staged}, opt_cfg)
        opt["step"] = jax.device_put(opt["step"], NamedSharding(mesh, P()))
        return rest, staged, opt

    from repro.optim import adamw as A

    @jax.jit
    def step_fn(rest, staged, opt, batch):
        # one jitted program per step: eager dispatch of the shard_map
        # collectives deadlocks the XLA CPU rendezvous (threads reach
        # different collectives in different orders)

        def wrapped(ps):
            full = dict(ps["rest"])
            return loss_fn(full, ps["staged"], batch)

        loss, grads = jax.value_and_grad(wrapped)(
            {"rest": rest, "staged": staged})
        merged = {"rest": rest, "staged": staged}
        new_p, new_opt, mets = A.apply_updates(merged, grads, opt, opt_cfg)
        return new_p["rest"], new_p["staged"], new_opt, \
            dict(mets, loss=loss)

    return init_fn, step_fn
