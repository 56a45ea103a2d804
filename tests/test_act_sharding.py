"""Activation-sharding constraint tests.

Regression for the silent-no-op bug: ``constrain`` compared
``str(AxisType.Auto) == "Auto"`` which is never true, so every activation
constraint in the framework lowered to nothing (16x replicated attention
on the production mesh — EXPERIMENTS.md §Perf #1).  These tests pin the
contract: constraints must appear in the lowered IR, priority must pick
the first dividing dim, and sharded programs must match unsharded ones
numerically.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.act_sharding import (BATCH, MODEL, axis_extent,
                                            constrain)
from repro.launch.mesh import make_mesh


def _mesh():
    return make_mesh((2, 4), ("data", "model"))


def _lowered_constraints(fn, *args):
    with jax.set_mesh(_mesh()):
        txt = jax.jit(fn).lower(*args).as_text()
    return [ln for ln in txt.splitlines()
            if "sharding_constraint" in ln or "mhlo.sharding" in ln]


def test_constraint_reaches_ir():
    x = jax.ShapeDtypeStruct((4, 16, 64), jnp.float32)
    lines = _lowered_constraints(
        lambda x: constrain(x, BATCH, MODEL, None).sum(), x)
    assert lines, "constrain() lowered to nothing (AxisType regression)"
    assert any("data" in ln and "model" in ln for ln in lines)


def test_priority_picks_first_dividing_dim():
    # dims: (batch=4, a=3, b=8, c=64): model extent 4 -> 'a' skipped (3%4),
    # 'b' gets it (8%4==0), 'c' must stay unconstrained
    x = jax.ShapeDtypeStruct((4, 3, 8, 64), jnp.float32)
    lines = _lowered_constraints(
        lambda x: constrain(x, BATCH, MODEL, MODEL, MODEL).sum(), x)
    assert lines
    (ln,) = [l for l in lines if "sharding_constraint" in l]
    # dim1 unconstrained, dim2 model
    assert '{"data"}, {?}, {"model"}, {?}' in ln, ln


def test_axis_extent():
    with jax.set_mesh(_mesh()):
        def f(x):
            assert axis_extent("model") == 4
            assert axis_extent("data") == 2
            assert axis_extent("pod") == 1
            return x
        jax.jit(f).lower(jax.ShapeDtypeStruct((4,), jnp.float32))
    assert axis_extent("model") == 1   # no ambient mesh


def test_sharded_matches_unsharded_numerics():
    from repro.kernels.flash_attention.chunked import chunked_attention
    B, S, H, K, D = 2, 256, 8, 4, 32
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, D), jnp.float32)
    ref = chunked_attention(q, k, v, causal=True, chunk=64)
    with jax.set_mesh(_mesh()):
        out = jax.jit(lambda q, k, v: chunked_attention(
            q, k, v, causal=True, chunk=64))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gqa_kv_expand_matches_grouped():
    """The TP kv-head expansion (H % TP == 0 but K, g % TP != 0) must be
    numerically identical to the grouped path."""
    from repro.kernels.flash_attention.chunked import chunked_attention
    # H=8 divides model extent 4; K=2 and g=4 both... g=4 divides; pick
    # H=8, K=2, g=4 on extent 8? Use mesh (1, 8): H=8%8==0, K=2%8!=0, g=4%8!=0
    mesh = make_mesh((1, 8), ("data", "model"))
    B, S, H, K, D = 2, 128, 8, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, D), jnp.float32)
    ref = chunked_attention(q, k, v, causal=True, chunk=32)   # no mesh
    with jax.set_mesh(mesh):
        out = jax.jit(lambda q, k, v: chunked_attention(
            q, k, v, causal=True, chunk=32))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["auto", "unconstrained"])
def test_moe_layout_numerics_match(layout):
    """MoE dispatch output must not depend on the expert-parallel layout."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import model as M, params as P
    cfg = get_config("mixtral-8x7b").reduced(num_layers=2, d_model=64,
                                             vocab_size=256)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, layout=layout))
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    out = M.forward_logits(params, cfg, {"tokens": toks})
    assert np.isfinite(np.asarray(out)).all()
    # layouts must agree with the default
    cfg0 = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, layout="auto"))
    out0 = M.forward_logits(params, cfg0, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(out), np.asarray(out0),
                               rtol=1e-6, atol=1e-6)


def test_whisper_cross_kv_cache_matches_legacy():
    """Warmed cross-KV decode must equal the legacy re-projection path."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import model as M, params as P
    cfg = get_config("whisper-medium").reduced(num_layers=2, d_model=64,
                                               vocab_size=128)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    frames = jax.random.normal(jax.random.PRNGKey(2),
                               (B, cfg.encoder_seq_len, cfg.d_model),
                               jnp.float32)
    enc = M.encoder_forward(params, cfg, frames, {})

    legacy = M.init_cache(cfg, B, S, dtype=jnp.float32)
    warmed = M.warm_cross_cache(params, cfg,
                                M.init_cache(cfg, B, S, dtype=jnp.float32),
                                enc)
    for i in range(S):
        t = toks[:, i:i + 1]
        lg_a, legacy = M.decode_step(params, cfg, legacy, t, jnp.int32(i),
                                     enc=enc)
        lg_b, warmed = M.decode_step(params, cfg, warmed, t, jnp.int32(i))
        # legacy projects K/V fresh in f32; the warmed path round-trips
        # K/V through the cache dtype and the bf16 attention inputs —
        # agreement is bounded by bf16 resolution, not exact
        np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                                   rtol=0.05, atol=0.05)


def test_vocab_padding_masks_and_divides():
    """Vocab padding (beyond-paper #8): padded logits are -inf, argmax and
    loss unaffected, and the padded vocab divides any TP extent <=128."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import model as M, params as P
    for arch in ("mamba2-130m", "granite-3-2b"):
        cfg = get_config(arch)
        assert cfg.padded_vocab_size % 128 == 0
        assert cfg.padded_vocab_size >= cfg.vocab_size

    cfg = get_config("mamba2-130m").reduced(num_layers=2, d_model=64,
                                            vocab_size=100)
    assert cfg.padded_vocab_size == 128
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 100)
    logits = M.forward_logits(params, cfg, {"tokens": toks})
    assert logits.shape[-1] == 128
    pad = np.asarray(logits)[..., 100:]
    assert (pad <= -1e29).all(), "pad region must be masked to -inf"
    # loss is finite and gradients flow
    loss, _ = M.cross_entropy(logits, toks)
    assert np.isfinite(float(loss))
