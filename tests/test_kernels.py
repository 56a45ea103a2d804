"""Per-kernel validation: Pallas (interpret mode) and the XLA chunked twin
swept over shapes/dtypes against the pure-jnp oracles (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import kernel as fa_kernel
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


# Lengths the tile rule covers with one tile, forced through several:
# B, S, H, K, D, causal, window, block_q, block_k
FA_TILED_CASES = [
    (1, 512, 4, 2, 64, True, 0, 128, 128),      # 4 x 4 tiles, GQA
    (1, 640, 2, 1, 64, True, 200, 128, 256),    # window, GQA 2
    (1, 520, 4, 2, 64, True, 0, 256, 128),      # padded q and kv
    (1, 520, 2, 2, 64, False, 0, 128, 256),     # non-causal, padded kv
    (1, 768, 2, 1, 64, True, 300, 256, 128),    # window over 3 q tiles
]


def _bhsd_pallas(causal, window, block_q, block_k):
    """flash_attention_bhsd in (B,S,H,D) layout with forced tiles."""
    def f(q, k, v):
        t = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
        return t(fa_kernel.flash_attention_bhsd(
            t(q), t(k), t(v), causal=causal, window=window,
            block_q=block_q, block_k=block_k, interpret=True))
    return f


@pytest.mark.parametrize("case", FA_TILED_CASES)
def test_flash_attention_pallas_tiled_vs_ref(case):
    """Online softmax across tiles, dead-tile clamping, diagonal-only
    masking and padding, against the oracle."""
    B, S, H, K, D, causal, window, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    out = _bhsd_pallas(causal, window, bq, bk)(q, k, v)
    ref = attention_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("case", FA_TILED_CASES)
def test_flash_attention_pallas_tiled_grad_vs_chunked(case):
    B, S, H, K, D, causal, window, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    w = jax.random.normal(ks[3], (B, S, H, D))
    f = _bhsd_pallas(causal, window, bq, bk)
    fp = lambda *a: (f(*a) * w).sum()                                  # noqa
    fc = lambda *a: (chunked_attention(*a, causal=causal, window=window,  # noqa
                                       chunk=64) * w).sum()
    gp = jax.grad(fp, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(fc, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gp, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=name)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 200),
                                           (False, 0), (False, 300)])
@pytest.mark.parametrize("q_len,kv_len,bq,bk", [
    (512, 512, 128, 128), (520, 520, 256, 128), (640, 640, 128, 256),
    (300, 700, 128, 256), (700, 600, 256, 128)])
def test_live_tile_bounds_match_the_element_mask(causal, window, q_len,
                                                 kv_len, bq, bk):
    """``_block_live`` is exactly "some pair of the tile is unmasked" (its
    rows include q padding, as the kernels compute them; its keys do
    not), ``_q_bounds`` inverts it, and ``_block_full`` is exactly "no
    pair of the tile is masked".  Every query has a key in these shapes
    (a tile whose window holds only kv padding counts as live)."""
    g = fa_kernel._Geom(causal, window, bq, bk, q_len, kv_len)
    nq, nk = -(-q_len // bq), -(-kv_len // bk)
    qp = np.arange(nq * bq)[:, None]
    kp = np.arange(nk * bk)[None, :]
    ok = (kp < kv_len) & (qp >= 0)
    if causal:
        ok = ok & (kp <= qp)
    if window:
        ok = ok & (kp > qp - window)
    for qi in range(nq):
        for ki in range(nk):
            tile = ok[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            live = bool(fa_kernel._block_live(qi, ki, g))
            assert live == bool(tile.any()), (qi, ki)
            lo, hi = fa_kernel._q_bounds(ki, g)
            assert live == (int(lo) <= qi <= int(hi)), (qi, ki)
            assert bool(fa_kernel._block_full(qi, ki, g)) == \
                bool(tile.all()), (qi, ki)


@pytest.mark.parametrize("S,D,H,K,window", [
    (2048, 64, 12, 12, 0), (4096, 128, 28, 4, 0), (8192, 128, 32, 8, 4096),
    (130, 96, 2, 2, 0), (520, 64, 4, 2, 0), (1920, 64, 4, 4, 0),
    (256, 64, 8, 2, 128), (640, 64, 2, 1, 200), (32768, 128, 8, 8, 0),
    (4096, 256, 8, 8, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tile_rule(S, D, H, K, window, dtype):
    """Multiples of 128 that divide the 128-padded length, at most that
    length, within the window by less than one 128 tile, within the VMEM
    budget; a function of the shapes alone."""
    tiles = fa_kernel.pick_tiles(S, S, D, D, window=window, dtype=dtype)
    padded = -(-S // 128) * 128
    itemsize = np.dtype(dtype).itemsize
    for name, (bq, bk) in tiles._asdict().items():
        for b in (bq, bk):
            assert b % 128 == 0 and b <= padded and padded % b == 0
            if window:
                assert b < window + 128
        assert fa_kernel._vmem_bytes(name, bq, bk, D, D, itemsize) \
            <= fa_kernel.VMEM_BUDGET
    assert fa_kernel.pick_tiles(np.int64(S), S, D, D, window=window,
                                dtype=jnp.dtype(dtype)) == tiles
    if S >= 2048 and D <= 128:      # every kernel's target fits
        assert tiles == fa_kernel.Tiles(**fa_kernel.TILE_TARGETS)


def test_tile_rule_is_what_every_caller_runs():
    """The (B,S,H,D) wrapper and the (B,H,S,D) entry point run the same
    rule's tiles: bit-equal to forcing them."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 384, 4, 64))
    k = jax.random.normal(ks[1], (1, 384, 2, 64))
    v = jax.random.normal(ks[2], (1, 384, 2, 64))
    bq, bk = fa_kernel.pick_tiles(384, 384, 64, 64, window=0,
                                  dtype=q.dtype).fwd
    a = flash_attention(q, k, v, causal=True, interpret=True)
    b = _bhsd_pallas(True, 0, bq, bk)(q, k, v)
    c = _bhsd_pallas(True, 0, None, None)(q, k, v)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


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
