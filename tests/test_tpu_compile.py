"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered with ``interpret=False`` for one chip
of a described (not attached) ``v5e:2x2`` topology and compiled by the TPU
compiler, which refuses what interpret mode accepts — block shapes whose
last two dims are not (8k, 128k) or whole, more VMEM than a kernel may
use.  The compiled text must hold the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest worker imports every
test file.  Keep all chip-compile tests in this one file.
"""

import dataclasses
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.decode import flash_decode_paged
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.quant8.kernel import dequantize_blocks, quantize_blocks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"     # no compiler logs on disk
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                      # no libtpu / no TPU backend
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described topology, with the persistent compile
    cache off: a compile for an unattached chip is written to it but can
    never be read back, and the next one warns."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# B, S, H, K, D, window: opt-125m (12 heads of 64), qwen2-7b (28/4 heads
# of 128) and mixtral-8x7b's attention (32/8 heads of 128, 4096 window),
# each at the tiles ``pick_tiles`` chooses for it
FA_SHAPES = {
    "opt-125m-s1024": (8, 1024, 12, 12, 64, 0),
    "opt-125m-s2048": (8, 2048, 12, 12, 64, 0),
    "qwen2-7b-s2048": (1, 2048, 28, 4, 128, 0),
    "mixtral-w4096-s8192": (1, 8192, 32, 8, 128, 4096),
}


def _qkv(one_chip, shape):
    B, S, H, K, D, _ = shape
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,  # noqa: E731
                                          sharding=one_chip)
    return sds(B, S, H, D), sds(B, S, K, D), sds(B, S, K, D)


@pytest.mark.parametrize("name", list(FA_SHAPES))
def test_flash_attention_forward_compiles(one_chip, name):
    window = FA_SHAPES[name][-1]
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window,
                                        interpret=False),
        *_qkv(one_chip, FA_SHAPES[name]))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("name", list(FA_SHAPES))
def test_flash_attention_backward_compiles(one_chip, name):
    window = FA_SHAPES[name][-1]

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=False)
        return out.astype(jnp.float32).sum()
    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                         *_qkv(one_chip, FA_SHAPES[name]))
    # forward (for the residuals) + the dq and dk/dv kernels
    assert txt.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_flash_decode_compiles(one_chip, chunk, kv_dtype):
    """qwen2-7b widths over a paged pool: 8 sequences, 16-token pages,
    C=1 decode and the engine's default C=8 prefill chunk."""
    B, H, K, D, bs, P, NB = 8, 28, 4, 128, 16, 512, 64
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt,  # noqa: E731
                                             sharding=one_chip)
    q = sds((B, H, D) if chunk == 1 else (B, chunk, H, D), jnp.float32)
    pages = sds((P, K, bs, D), jnp.dtype(kv_dtype))
    args = [q, pages, pages, sds((B, NB), jnp.int32), sds((B,), jnp.int32)]
    if kv_dtype == "int8":
        scales = sds((P, K, bs), jnp.float32)
        fn = lambda q, kp, vp, bt, sl, ks, vs: flash_decode_paged(  # noqa
            q, kp, vp, bt, sl, k_scale=ks, v_scale=vs, interpret=False)
        args += [scales, scales]
    else:
        fn = lambda q, kp, vp, bt, sl: flash_decode_paged(  # noqa: E731
            q, kp, vp, bt, sl, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("op", ["quantize", "dequantize"])
def test_quant8_compiles(one_chip, op):
    """A 2.4M-element gradient leaf (opt-125m's d_model x d_ff)."""
    n = 768 * 3072
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt,  # noqa: E731
                                             sharding=one_chip)
    if op == "quantize":
        txt = _compiled_text(
            lambda x: quantize_blocks(x, block=256, interpret=False),
            sds((n,), jnp.float32))
    else:
        txt = _compiled_text(
            lambda q, s: dequantize_blocks(q, s, block=256,
                                           interpret=False),
            sds((n,), jnp.int8), sds((n // 256,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_train_step_kernels_keep_their_names_and_scope(one_chip,
                                                        monkeypatch):
    """The whole training step at opt-125m widths (2 layers, batch 1,
    S 2048, ``attn_impl="pallas"``): the benchmark's kernel readers still
    find the FA-2 forward and backward instructions by name and output
    dtype, and those instructions carry the ``attention_core`` scope."""
    from repro.configs import get_config
    from repro.kernels.flash_attention import ops
    from repro.models import params as PM
    from repro.optim import adamw
    from repro.train.step import make_train_step
    root = Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(root))
    from benchmarks.chip import harness
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # compile, not interpret
    cfg = dataclasses.replace(get_config("opt-125m"), num_layers=2)
    oc = adamw.OptConfig()
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                         sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: PM.init_params(cfg, k), jax.random.PRNGKey(0)))
    opt = jax.tree.map(sds, jax.eval_shape(
        lambda p: adamw.init_opt_state(p, oc), params))
    batch = {k: jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    txt = _compiled_text(make_train_step(cfg, oc, attn_impl="pallas"),
                         params, opt, batch)
    lines = [ln.strip() for ln in txt.splitlines()]
    for reader, least in (("attn_fwd_roofline.train", 1),
                          ("attn_bwd_roofline.train", 2)):
        rx = re.compile(harness.metric_reader(reader).PATTERN)
        hits = [ln for ln in lines if rx.search(ln)]
        assert len(hits) >= least, reader
        for ln in hits:
            op = re.search(r'op_name="([^"]*)"', ln)
            assert op and "/attention_core/" in op.group(1), ln[:200]
