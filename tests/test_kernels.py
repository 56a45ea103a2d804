"""Per-kernel validation: Pallas (interpret mode) and the XLA chunked twin
swept over shapes/dtypes against the pure-jnp oracles (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.chunked import chunked_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.quant8.kernel import dequantize_blocks, quantize_blocks
from repro.kernels.quant8.ops import dequantize, quantize
from repro.kernels.quant8.ref import dequantize_reference, quantize_reference
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_reference, ssd_step

FA_CASES = [
    # B, S, H, K, D, causal, window
    (2, 256, 4, 2, 64, True, 0),
    (1, 384, 4, 4, 128, True, 0),
    (2, 256, 8, 2, 64, True, 128),
    (1, 200, 2, 1, 64, False, 0),
    (1, 130, 2, 2, 96, True, 0),
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_pallas_vs_ref(case, dtype):
    B, S, H, K, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_pallas_grad_vs_chunked(case):
    """Fused Pallas FA-2 backward (interpret) == chunked custom-VJP grads
    to <=1e-3 in fp32 across causal / sliding-window / GQA / padded-seq
    (acceptance criterion for the pallas training path)."""
    B, S, H, K, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    w = jax.random.normal(ks[3], (B, S, H, D))   # non-trivial cotangent

    fp = lambda *a: (flash_attention(*a, causal=causal, window=window,  # noqa
                                     interpret=True) * w).sum()
    fc = lambda *a: (chunked_attention(*a, causal=causal, window=window,  # noqa
                                       chunk=64) * w).sum()
    gp = jax.grad(fp, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(fc, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gp, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=name)


def test_flash_attention_pallas_grad_bf16_runs():
    """bf16 primals: backward runs and cotangents keep the primal dtype
    (custom_vjp contract)."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.bfloat16)
    f = lambda *a: flash_attention(*a, causal=True,                     # noqa
                                   interpret=True).astype(jnp.float32).sum()
    gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    assert gq.dtype == jnp.bfloat16 and gk.dtype == jnp.bfloat16 \
        and gv.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(gq, np.float32)).all()


def test_train_step_accepts_donated_buffers():
    """The jitted train step runs with donate_argnums=(params, opt_state):
    two consecutive steps reuse the chain of donated buffers without error
    and keep producing finite losses."""
    from repro.configs.opt import opt_config
    from repro.optim import adamw
    from repro.train.step import make_train_step

    cfg = opt_config("opt-125m").reduced(num_layers=1, d_model=64,
                                         vocab_size=256)
    from repro.models import params as PM
    params = PM.init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = adamw.OptConfig(warmup_steps=1, decay_steps=4)
    opt_state = adamw.init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    for _ in range(2):
        params, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("case", FA_CASES)
def test_chunked_attention_fwd_and_grad(case):
    B, S, H, K, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))

    out = chunked_attention(q, k, v, causal=causal, window=window, chunk=64)
    ref = attention_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    f = lambda *a: chunked_attention(*a, causal=causal, window=window,  # noqa
                                     chunk=64).sum()
    g = lambda *a: attention_reference(*a, causal=causal,               # noqa
                                       window=window).astype(jnp.float32).sum()
    gc = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


SSD_CASES = [
    # b, s, h, g, p, n, chunk
    (2, 256, 4, 1, 64, 32, 64),
    (1, 128, 8, 2, 32, 128, 32),
    (2, 100, 4, 4, 64, 16, 32),
    (1, 512, 2, 1, 128, 64, 128),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_vs_ref(case, dtype):
    b, s, h, g, p, n, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, g, n), dtype)
    C = jax.random.normal(ks[4], (b, s, g, n), dtype)
    out = ssd(x, dt, A, B, C, chunk, interpret=True)
    ref = ssd_reference(x, dt, A, B, C, chunk)
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-6
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32) / scale,
                               np.asarray(ref, np.float32) / scale,
                               rtol=tol, atol=tol)


def test_ssd_step_matches_full_scan():
    """Sequential single-step recurrence == chunked full-sequence scan."""
    b, s, h, g, p, n = 1, 32, 2, 1, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    full = ssd_reference(x, dt, A, B, C, chunk_size=8)
    state = jnp.zeros((b, h, p, n))
    outs = []
    for t in range(s):
        y, state = ssd_step(state, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        outs.append(y)
    seq = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,block", [(65536, 256), (512 * 512, 512),
                                     (1 << 16, 128)])
def test_quant8_kernel_vs_ref(n, block):
    x = jax.random.normal(jax.random.PRNGKey(4), (n,)) * 3
    qk, sk = quantize_blocks(x, block=block, interpret=True)
    qr, sr = quantize_reference(x, block)
    assert bool(jnp.all(qk == qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    dk = dequantize_blocks(qk, sk, block=block, interpret=True)
    dr = dequantize_reference(qr, sr, block)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), rtol=1e-6)


@pytest.mark.parametrize("shape", [(100, 777), (3, 5, 7), (65536,)])
def test_quant8_roundtrip_error_bound(shape):
    x = jax.random.normal(jax.random.PRNGKey(5), shape) * 2
    q, s, sh = quantize(x)
    xr = dequantize(q, s, sh)
    # blockwise bound: |err| <= scale/2 per block
    flat = np.asarray(x, np.float32).reshape(-1)
    pad = (-flat.shape[0]) % 256
    fb = np.pad(flat, (0, pad)).reshape(-1, 256)
    bound = np.repeat(np.abs(fb).max(1) / 127 * 0.5 + 1e-6,
                      256)[:flat.shape[0]]
    err = np.abs(np.asarray(xr, np.float32).reshape(-1) - flat)
    assert np.all(err <= bound)


@pytest.mark.parametrize("shape", [(100, 777), (3, 5, 7), (300, 256)])
def test_quant8_irregular_rows_run_the_kernel(shape):
    """Row counts that are not a multiple of the row tile (304, 1 and 300
    rows of 256) are padded to whole tiles: the Pallas kernel runs, its
    codes on the real rows equal the oracle's, and its scales agree to
    the ULP by which XLA's amax * (1/127) may differ from amax / 127."""
    x = jax.random.normal(jax.random.PRNGKey(6), shape) * 2
    assert "pallas_call" in str(jax.make_jaxpr(quantize)(x))
    q, s, sh = quantize(x)
    n = x.size
    rows = -(-n // 256)
    flat = jnp.pad(x.reshape(-1), (0, rows * 256 - n))
    qr, sr = quantize_reference(flat, 256)
    assert bool(jnp.all(q[:rows * 256] == qr))
    np.testing.assert_allclose(np.asarray(s[:rows]), np.asarray(sr),
                               rtol=1e-6)
    assert not np.asarray(q[rows * 256:]).any()
    np.testing.assert_allclose(
        np.asarray(dequantize(q, s, sh)),
        np.asarray(dequantize_reference(qr, sr, 256)[:n].reshape(shape)),
        rtol=1e-6)
