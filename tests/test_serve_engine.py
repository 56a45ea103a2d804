"""Paged-KV serving engine: cache parity, flash-decode numerics, block
allocator properties, continuous-batching end-to-end, sampler, and the
no-recompile contract of ``greedy_generate``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.flash_attention.chunked import chunked_attention
from repro.kernels.flash_attention.decode import (flash_decode_paged,
                                                 paged_attention_reference)
from repro.models import model as M
from repro.models import params as P
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.paged_cache import BlockAllocator, PagedKVCache, blocks_for
from repro.serve.sampling import SamplingParams, sample_tokens
from repro.serve.step import greedy_generate, jitted_decode_step

from conftest import tiny


def _cfg(arch, **patch):
    cfg = tiny(get_config(arch))
    return dataclasses.replace(cfg, **patch) if patch else cfg


# --------------------------------------------------------------------------- #
# Paged vs dense decode parity
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("arch,patch", [
    ("qwen2-7b", dict(num_kv_heads=2)),          # GQA (+ qkv bias)
    ("mixtral-8x7b", dict(sliding_window=6)),    # SWA + MoE decoder
    ("opt-125m", {}),                            # learned positions
])
def test_paged_vs_dense_decode_logits(arch, patch):
    """Teacher-forcing the same prompt through decode_step (dense cache)
    and decode_step_paged (block-table cache) yields identical logits."""
    cfg = _cfg(arch, **patch)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    B, S, bs = 2, 11, 4
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)

    dense = M.init_cache(cfg, B, 16, jnp.float32)
    kv = PagedKVCache(num_blocks=12, block_size=bs, max_slots=B,
                      max_blocks_per_seq=4)
    pages = M.init_paged_cache(cfg, 12, bs, jnp.float32)
    for s in range(B):
        kv.open_slot(s)

    for i in range(S):
        ld, dense = M.decode_step(params, cfg, dense, prompt[:, i:i + 1],
                                  jnp.int32(i))
        for s in range(B):
            assert kv.ensure_capacity(s)
        lp, pages = M.decode_step_paged(
            params, cfg, pages, prompt[:, i:i + 1],
            jnp.asarray(kv.device_tables()), jnp.asarray(kv.seq_lens()))
        for s in range(B):
            kv.commit_token(s)
        np.testing.assert_allclose(np.asarray(ld), np.asarray(lp),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"{arch} step {i}")


# --------------------------------------------------------------------------- #
# Flash-decode kernel numerics
# --------------------------------------------------------------------------- #

DECODE_CASES = [
    # H, K, D, bs, lens, window
    (4, 2, 64, 8, (17, 40), 0),          # GQA
    (4, 4, 32, 4, (1, 26), 0),           # MHA, fresh seq
    (8, 2, 64, 16, (33, 64), 20),        # GQA + sliding window
    (2, 1, 16, 4, (5, 12), 5),           # window < block
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_flash_decode_matches_chunked_reference(case):
    """Pallas flash-decode (interpret) over scattered pages == the chunked
    XLA flash kernel's last causal row over the equivalent dense KV."""
    H, K, D, bs, lens, window = case
    B = len(lens)
    nb = blocks_for(max(lens), bs)
    P_pool = B * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, D))
    k_pages = jax.random.normal(ks[1], (P_pool, K, bs, D))
    v_pages = jax.random.normal(ks[2], (P_pool, K, bs, D))
    # disjoint per-sequence block tables (page 0 = null)
    bt = (1 + np.arange(B * nb, dtype=np.int32)).reshape(B, nb)
    sl = jnp.asarray(lens, jnp.int32)

    out = flash_decode_paged(q, k_pages, v_pages, jnp.asarray(bt), sl,
                             window=window, pages_per_split=3,
                             interpret=True)
    ref = paged_attention_reference(q, k_pages, v_pages, jnp.asarray(bt),
                                    sl, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # cross-check against chunked.py on the gathered dense layout: the
    # decode output is the last causal row of full-sequence attention
    for b, L in enumerate(lens):
        kd = jnp.swapaxes(k_pages[bt[b]], 1, 2).reshape(-1, K, D)[None, :L]
        vd = jnp.swapaxes(v_pages[bt[b]], 1, 2).reshape(-1, K, D)[None, :L]
        qd = jnp.zeros((1, L, H, D)).at[:, L - 1].set(q[b])
        full = chunked_attention(qd, kd, vd, causal=True, window=window,
                                 chunk=8)
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(full[0, L - 1]),
                                   rtol=2e-5, atol=2e-5, err_msg=f"seq {b}")


# --------------------------------------------------------------------------- #
# Block allocator / paged-cache properties
# --------------------------------------------------------------------------- #

def _exercise_allocator(seed: int, num_blocks: int = 17, block_size: int = 4,
                        max_slots: int = 5, steps: int = 300):
    """Random alloc/append/free op machine; checks the paging invariants."""
    rng = np.random.RandomState(seed)
    kv = PagedKVCache(num_blocks=num_blocks, block_size=block_size,
                      max_slots=max_slots, max_blocks_per_seq=6)
    usable = kv.allocator.num_usable
    for _ in range(steps):
        op = rng.randint(3)
        free_slots = kv.free_slots()
        live = [i for i in range(max_slots) if i not in free_slots]
        if op == 0 and free_slots:
            kv.open_slot(free_slots[0])
        elif op == 1 and live:
            slot = live[rng.randint(len(live))]
            before = kv.allocator.num_free
            ok = kv.ensure_capacity(slot)
            if ok:
                kv.commit_token(slot)
                t = kv.table(slot)
                assert t.num_tokens <= t.allocated_tokens(block_size)
            else:
                # OOM must coincide with exhaustion (pool or table limit)
                t = kv.table(slot)
                assert (before == 0 or len(t.blocks) >= 6)
        elif op == 2 and live:
            kv.close_slot(live[rng.randint(len(live))])

        # invariants: conservation + disjointness + null page untouched
        tables = [kv.table(i) for i in range(max_slots)
                  if i not in kv.free_slots()]
        held = [b for t in tables for b in t.blocks]
        assert len(held) == len(set(held)), "block double-booked"
        assert 0 not in held, "null page allocated"
        assert len(held) + kv.allocator.num_free == usable, "leak"
        assert kv.allocator.peak_blocks_in_use >= len(held)
        st = kv.stats()
        assert 0 <= st["frag_frac"] <= 1 and st["frag_tokens"] >= 0
    for i in range(max_slots):
        if i not in kv.free_slots():
            kv.close_slot(i)
    assert kv.allocator.num_free == usable, "blocks not all returned"


@pytest.mark.parametrize("seed", range(5))
def test_block_allocator_invariants_random_ops(seed):
    _exercise_allocator(seed)


def test_block_allocator_invariants_hypothesis():
    """Same op machine driven by hypothesis where available; containers
    without it run a seeded sweep over the SAME parameter space instead
    of skipping — the invariants are checked either way."""
    try:
        import hypothesis as hyp
        from hypothesis import strategies as st
    except ImportError:
        rng = np.random.RandomState(1234)
        for _ in range(30):
            _exercise_allocator(int(rng.randint(0, 2**16)),
                                num_blocks=int(rng.randint(3, 41)),
                                block_size=int(rng.randint(1, 9)),
                                steps=60)
        return

    @hyp.given(seed=st.integers(0, 2**16), blocks=st.integers(3, 40),
               bs=st.integers(1, 8))
    @hyp.settings(max_examples=30, deadline=None)
    def prop(seed, blocks, bs):
        _exercise_allocator(seed, num_blocks=blocks, block_size=bs,
                            steps=60)

    prop()


# --------------------------------------------------------------------------- #
# Prefix sharing: refcount/CoW state machine + index semantics
# --------------------------------------------------------------------------- #

def _drain_copies(kv, content):
    """Mirror the engine's CoW drain: device page copy -> host simulation."""
    for src, dst in kv.take_pending_copies():
        content[dst] = list(content.get(src, []))


def _exercise_sharing_machine(seed: int, num_blocks: int = 17,
                              block_size: int = 4, max_slots: int = 5,
                              steps: int = 300, vocab: int = 5):
    """Random open/append/close machine over the prefix-sharing cache with
    a host-side simulation of device page contents.  Invariants:

    * per-block refcount == number of live tables mapping the block; a
      block re-enters the free list only at refcount zero (no leak, no
      double-booking beyond the refcounts),
    * a position is only ever written into a block whose refcount is 1 —
      shared blocks are never mutated in place (CoW forked first),
    * every prefix-index hit maps blocks whose simulated contents equal
      the prompt (recycling never leaves stale entries behind),
    * closing everything returns the whole pool to the free list.

    A tiny token alphabet forces heavy prefix collision so the index,
    CoW, and cold-recycling paths all fire.
    """
    rng = np.random.RandomState(seed)
    kv = PagedKVCache(num_blocks=num_blocks, block_size=block_size,
                      max_slots=max_slots, max_blocks_per_seq=6)
    usable = kv.allocator.num_usable
    content: dict = {}                   # block -> tokens written, in order
    remaining: dict = {}                 # slot -> prompt tokens still to feed
    forks_seen = 0
    for _ in range(steps):
        op = rng.randint(3)
        free_slots = kv.free_slots()
        live = [i for i in range(max_slots) if i not in free_slots]
        if op == 0 and free_slots:
            plen = int(rng.randint(2, 3 * block_size))
            prompt = list(map(int, rng.randint(0, vocab, plen)))
            if not kv.can_admit(prompt):
                continue
            slot = free_slots[0]
            cached = kv.open_slot(slot, prompt)
            t = kv.table(slot)
            assert cached <= len(prompt) - 1, "last token must be recomputed"
            for p in range(cached):
                blk = t.blocks[p // block_size]
                assert content[blk][p % block_size] == prompt[p], \
                    "prefix-index hit served stale KV"
            remaining[slot] = prompt[cached:]
        elif op == 1 and live:
            slot = live[int(rng.randint(len(live)))]
            t = kv.table(slot)
            before_forks = kv.cow_forks
            if kv.ensure_capacity(slot):
                _drain_copies(kv, content)
                forks_seen += kv.cow_forks - before_forks
                tail = t.blocks[t.num_tokens // block_size]
                assert kv.allocator.refcount(tail) == 1, \
                    "write into a shared block (missed CoW fork)"
                rem = remaining.get(slot)
                tok = rem.pop(0) if rem else int(rng.randint(0, vocab))
                off = t.num_tokens % block_size
                buf = content.setdefault(tail, [])
                while len(buf) <= off:
                    buf.append(-1)
                buf[off] = tok
                kv.commit_token(slot, tok)
        elif op == 2 and live:
            slot = live[int(rng.randint(len(live)))]
            kv.close_slot(slot)
            remaining.pop(slot, None)

        # refcount accounting: each live table reference is one holder
        refs: dict = {}
        for i in range(max_slots):
            if i in kv.free_slots():
                continue
            for b in kv.table(i).blocks:
                refs[b] = refs.get(b, 0) + 1
        for b in range(1, num_blocks):
            assert kv.allocator.refcount(b) == refs.get(b, 0), \
                f"block {b}: refcount drift"
        assert 0 not in refs, "null page mapped"
        assert kv.allocator.blocks_in_use == len(refs)
        assert len(refs) + kv.allocator.num_free == usable, "leak"
        fl = kv.allocator._free
        assert len(fl) == len(set(fl)), "free-list duplicate"
    for i in range(max_slots):
        if i not in kv.free_slots():
            kv.close_slot(i)
    assert kv.allocator.num_free == usable, "blocks not all returned"
    return forks_seen


@pytest.mark.parametrize("seed", range(5))
def test_prefix_sharing_invariants_random_ops(seed):
    _exercise_sharing_machine(seed)


def test_prefix_sharing_invariants_sweep():
    """Hypothesis-style parameter sweep (seeded: the container may not
    ship hypothesis) — small pools force recycling of cached blocks, and
    across the sweep the CoW path must actually fire."""
    try:
        import hypothesis as hyp
        from hypothesis import strategies as st
    except ImportError:
        rng = np.random.RandomState(99)
        forks = 0
        for _ in range(25):
            forks += _exercise_sharing_machine(
                int(rng.randint(0, 2**16)),
                num_blocks=int(rng.randint(5, 30)),
                block_size=int(rng.randint(2, 6)),
                steps=80)
        assert forks > 0, "sweep never exercised copy-on-write"
        return

    @hyp.given(seed=st.integers(0, 2**16), blocks=st.integers(5, 29),
               bs=st.integers(2, 5))
    @hyp.settings(max_examples=25, deadline=None)
    def prop(seed, blocks, bs):
        _exercise_sharing_machine(seed, num_blocks=blocks, block_size=bs,
                                  steps=80)

    prop()


def test_refcount_alloc_incref_decref_cold():
    a = BlockAllocator(num_blocks=5, block_size=4)
    [b] = a.alloc(1)
    assert a.refcount(b) == 1
    a.incref(b)
    assert a.refcount(b) == 2
    assert a.decref(b) == 1 and b not in a._free   # still held: not freed
    a.decref(b, cold=True)
    assert a.refcount(b) == 0 and a._free[0] == b  # parked cold (LIFO far end)
    with pytest.raises(ValueError):
        a.decref(b)                                # double free
    a.incref(b)                                    # revived from the free list
    assert a.refcount(b) == 1 and b not in a._free
    a.decref(b)
    assert a.num_free == a.num_usable


def test_prefix_hit_cow_fork_and_sole_holder_divergence():
    """Two sequences share a registered prefix; appending into the shared
    partial tail CoW-forks it (page copy queued, shared page untouched),
    while a sole holder diverges in place and just drops the entry."""
    kv = PagedKVCache(num_blocks=16, block_size=4, max_slots=3,
                      max_blocks_per_seq=6)
    sys_p = [1, 2, 3, 4, 5, 6]                     # 1.5 blocks
    kv.open_slot(0, sys_p)
    for tok in sys_p:
        assert kv.ensure_capacity(0)
        kv.commit_token(0, tok)
    b_full, b_tail = kv.table(0).blocks
    kv.close_slot(0)                               # registers [1..4] and (5,6)

    assert kv.open_slot(1, sys_p + [9, 9]) == 6    # full block + partial tail
    assert kv.open_slot(2, sys_p + [8, 8]) == 6
    t1, t2 = kv.table(1), kv.table(2)
    assert t1.blocks == [b_full, b_tail] == t2.blocks
    assert kv.allocator.refcount(b_tail) == 2

    assert kv.ensure_capacity(1)                   # write offset 2, shared
    assert kv.cow_forks == 1
    copies = kv.take_pending_copies()
    assert copies and copies[0][0] == b_tail
    fresh = copies[0][1]
    assert t1.blocks == [b_full, fresh]            # fork replaced the tail
    assert t2.blocks == [b_full, b_tail], "shared block mutated in place"
    kv.commit_token(1, 9)

    assert kv.ensure_capacity(2)                   # now sole holder of b_tail
    assert kv.cow_forks == 1 and not kv.pending_copies
    assert b_tail not in kv._node, "diverging tail must leave the index"
    kv.commit_token(2, 8)

    for s in (1, 2):
        kv.close_slot(s)
    assert kv.allocator.num_free == kv.allocator.num_usable


def test_recycled_cached_block_never_matches_stale():
    """Cached blocks park cold and are recycled last; once recycled their
    index entries (and descendants') are gone, so a later identical
    prompt recomputes instead of mapping stale pages."""
    kv = PagedKVCache(num_blocks=6, block_size=2, max_slots=2,
                      max_blocks_per_seq=5)
    prompt = [1, 2, 3, 4, 5, 6]                    # 3 full blocks
    kv.open_slot(0, prompt)
    for tok in prompt:
        assert kv.ensure_capacity(0)
        kv.commit_token(0, tok)
    kv.close_slot(0)
    assert len(kv.prefix_index) == 3               # chain cached, all cold
    # burn the whole pool with an unrelated prompt -> recycles cached blocks
    kv.open_slot(1)
    for tok in range(10, 10 + 2 * 5):
        assert kv.ensure_capacity(1)
        kv.commit_token(1, tok)
    kv.close_slot(1)
    assert kv.open_slot(0, prompt) == 0, "stale prefix entry survived"
    assert all(b not in kv._node or kv._node[b].parent == 0
               or kv._node[b].parent in kv._node
               for b in list(kv._node)), "dangling chain"
    kv.close_slot(0)


def test_allocator_oom_and_double_free():
    a = BlockAllocator(num_blocks=4, block_size=8)
    got = a.alloc(3)
    assert sorted(got) == [1, 2, 3] and a.num_free == 0
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(got)
    with pytest.raises(ValueError):
        a.free([1])
    with pytest.raises(ValueError):
        a.free([0])


# --------------------------------------------------------------------------- #
# int8 KV blocks
# --------------------------------------------------------------------------- #

def test_quant8_kv_roundtrip_on_kv_blocks():
    """Per-vector symmetric int8 on KV-shaped pages: round-trip error is
    bounded by half a quantization step per element."""
    from repro.kernels.quant8.ops import dequantize_kv, quantize_kv
    x = jax.random.normal(jax.random.PRNGKey(0), (7, 16, 2, 64)) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == x.shape[:-1]
    y = dequantize_kv(q, s, jnp.float32)
    step = np.asarray(s)[..., None]                # scale == amax / 127
    # Half a step, plus one fp32 ULP of the largest code (127): fl(x / s)
    # can round a true 22.5000009 onto the 22.5 tie, which round-half-even
    # sends the far way, and fl(q * s) rounds once more.  XLA folds
    # amax / 127 into amax * (1/127), so s is itself a ULP off amax / 127
    # and such ties do occur (0.5000014 steps on this input).
    ulp127 = 127 * np.finfo(np.float32).eps
    assert np.max(np.abs(np.asarray(y - x)) / step) <= 0.5 + ulp127
    # all-zero vectors stay exactly zero (scale clamps to 1, not 0/0)
    q0, s0 = quantize_kv(jnp.zeros((3, 4, 1, 8)))
    assert np.all(np.asarray(q0) == 0) and np.all(np.asarray(s0) == 1.0)
    assert np.all(np.asarray(dequantize_kv(q0, s0, jnp.float32)) == 0)


@pytest.mark.parametrize("arch,patch", [
    ("qwen2-7b", dict(num_kv_heads=2)),          # GQA
    ("mixtral-8x7b", dict(sliding_window=6)),    # SWA + MoE
])
@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_paged_int8_cache_close_to_fp(arch, patch, impl):
    """Teacher-forcing through an int8 paged cache (quantize at append,
    dequantize inside the attention gather / Pallas kernel) tracks the
    fp32 cache within the quantization budget, for both decode impls."""
    cfg = _cfg(arch, **patch)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    B, S, bs = 2, 11, 4
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    pools = {"fp": M.init_paged_cache(cfg, 12, bs, jnp.float32),
             "q": M.init_paged_cache(cfg, 12, bs, jnp.int8)}
    qleaf = [l for l in jax.tree.leaves(pools["q"]) if l.dtype == jnp.int8]
    assert qleaf, "int8 pool must store int8 pages"
    kv = PagedKVCache(num_blocks=12, block_size=bs, max_slots=B,
                      max_blocks_per_seq=4)
    for s in range(B):
        kv.open_slot(s)
    last = {}
    for i in range(S):
        for s in range(B):
            assert kv.ensure_capacity(s)
        bt = jnp.asarray(kv.device_tables())
        sl = jnp.asarray(kv.seq_lens())
        for name in pools:
            last[name], pools[name] = M.decode_step_paged(
                params, cfg, pools[name], prompt[:, i:i + 1], bt, sl,
                attn_impl=impl)
        for s in range(B):
            kv.commit_token(s)
    err = float(jnp.max(jnp.abs(last["q"] - last["fp"])))
    assert err <= 5e-2, f"{arch}/{impl}: int8 KV logits off by {err}"


def test_int8_pool_bytes_ratio():
    """int8 pages + fp32 per-vector scales weigh (D+4)/(2D) of the bf16
    pool — under the 0.55x acceptance bound for D >= 64."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    bf = sum(l.size * l.dtype.itemsize
             for l in jax.tree.leaves(M.init_paged_cache(cfg, 8, 16,
                                                         jnp.bfloat16)))
    q = sum(l.size * l.dtype.itemsize
            for l in jax.tree.leaves(M.init_paged_cache(cfg, 8, 16,
                                                        jnp.int8)))
    D = cfg.resolved_head_dim
    assert q / bf == pytest.approx((D + 4) / (2 * D))
    assert q / bf <= 0.55


def test_shared_prefix_pages_bit_identical_to_private():
    """A sequence admitted through the prefix index maps pages written by
    the ORIGINAL prefill; recomputing the same prompt privately (same
    chunking) produces bit-identical page contents — sharing changes
    where KV lives, never what it holds."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    bs = 4
    prompt = list(range(1, 10))                    # 2 full blocks + tail

    def prefill(kv, pages, slot, toks):
        for tok in toks:
            assert kv.ensure_capacity(slot)
            t = jnp.zeros((1, 1), jnp.int32).at[0, 0].set(tok)
            _, pages = M.decode_step_paged(
                params, cfg, pages, t, jnp.asarray(kv.device_tables()),
                jnp.asarray(kv.seq_lens()))
            kv.commit_token(slot, tok)
        return pages

    # sharing path: seq 0 prefills + closes, seq 1 re-opens the same prompt
    kv_s = PagedKVCache(num_blocks=12, block_size=bs, max_slots=1,
                        max_blocks_per_seq=4)
    pages_s = M.init_paged_cache(cfg, 12, bs, jnp.float32)
    kv_s.open_slot(0, prompt)
    pages_s = prefill(kv_s, pages_s, 0, prompt)
    kv_s.close_slot(0)
    cached = kv_s.open_slot(0, prompt)
    assert cached == len(prompt) - 1               # all but the last token
    shared_blocks = list(kv_s.table(0).blocks)

    # private path: fresh cache, sharing off
    kv_p = PagedKVCache(num_blocks=12, block_size=bs, max_slots=1,
                        max_blocks_per_seq=4, prefix_sharing=False)
    pages_p = M.init_paged_cache(cfg, 12, bs, jnp.float32)
    kv_p.open_slot(0)
    pages_p = prefill(kv_p, pages_p, 0, prompt)
    private_blocks = list(kv_p.table(0).blocks)

    leaves_s, leaves_p = jax.tree.leaves(pages_s), jax.tree.leaves(pages_p)
    compared = 0
    for ls, lp in zip(leaves_s, leaves_p):
        if ls.ndim < 4 or ls.shape[-2] != bs:
            continue                               # not a page pool leaf
        a, b = np.asarray(ls), np.asarray(lp)
        for bi in range(cached // bs):             # fully-cached blocks only
            sa = a[..., shared_blocks[bi], :, :, :]
            sb = b[..., private_blocks[bi], :, :, :]
            assert np.array_equal(sa, sb), "shared page != private recompute"
            compared += 1
    assert compared > 0


# --------------------------------------------------------------------------- #
# Engine end-to-end
# --------------------------------------------------------------------------- #

def _mixed_requests(cfg, n=5):
    prompts = [list(np.random.RandomState(i).randint(
        0, cfg.vocab_size, 3 + 3 * i)) for i in range(n)]
    max_new = [5 + (3 * i) % 7 for i in range(n)]
    return prompts, max_new, [
        Request(uid=f"r{i}", prompt=p, max_new=m)
        for i, (p, m) in enumerate(zip(prompts, max_new))]


def test_engine_matches_sequential_greedy():
    """Continuous batching (mixed lengths, fewer slots than requests)
    reproduces per-request dense greedy decoding exactly."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    prompts, max_new, reqs = _mixed_requests(cfg)
    eng = ServeEngine(params, cfg, EngineConfig(
        max_slots=3, block_size=4, num_blocks=40, max_blocks_per_seq=10))
    out = eng.run(reqs)
    assert set(out) == {r.uid for r in reqs}
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        ref = greedy_generate(params, cfg, jnp.asarray([p], jnp.int32), m)
        assert out[f"r{i}"].tokens == list(map(int, np.asarray(ref)[0, len(p):]))
    assert eng.kv.allocator.num_free == eng.kv.allocator.num_usable


def test_engine_preemption_under_memory_pressure():
    """A pool too small for all admitted sequences forces recompute
    preemption; results still match dense greedy and no blocks leak."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    prompts, max_new, reqs = _mixed_requests(cfg)
    eng = ServeEngine(params, cfg, EngineConfig(
        max_slots=3, block_size=4, num_blocks=9, max_blocks_per_seq=8))
    out = eng.run(reqs)
    assert sum(c.preemptions for c in out.values()) > 0, \
        "pool was sized to force preemption"
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        ref = greedy_generate(params, cfg, jnp.asarray([p], jnp.int32), m)
        assert out[f"r{i}"].tokens == list(map(int, np.asarray(ref)[0, len(p):]))
    assert eng.kv.allocator.num_free == eng.kv.allocator.num_usable


def test_engine_admission_rejects_oversized_request():
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, EngineConfig(
        max_slots=2, block_size=4, num_blocks=6, max_blocks_per_seq=4))
    with pytest.raises(ValueError):
        eng.submit(Request(uid="big", prompt=list(range(30)), max_new=10))
    with pytest.raises(ValueError):
        eng.submit(Request(uid="empty", prompt=[1, 2], max_new=0))


def test_engine_stats_window_and_frag_peaks():
    """reset_stats() starts a clean measurement window after warmup, and
    fragmentation/utilization are sampled at their per-step peaks (the
    instantaneous numbers are zero once every slot is evicted)."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, EngineConfig(
        max_slots=2, block_size=4, num_blocks=20, max_blocks_per_seq=8))
    eng.run([Request(uid="warm", prompt=[1, 2, 3], max_new=2)])
    warm_j = eng.monitor.total_j
    assert warm_j > 0
    eng.reset_stats()
    assert eng.monitor.total_j == 0 and eng.steps == 0
    assert not eng.completions
    eng.run([Request(uid="a", prompt=[5, 6, 7], max_new=4)])
    s = eng.stats()
    assert s["steps"] > 0 and s["energy_j"] > 0
    # prompt 3 + 4 new = 7 tokens in 4-token blocks -> tail slot unwritten
    assert s["frag_tokens_peak"] >= 1
    assert 0 < s["utilization_peak"] <= 1
    assert s["peak_cache_bytes"] > 0


def test_engine_token_by_token_mode_matches_greedy():
    """prefill_chunk=1 + sharing off is the pre-fast-path engine (the
    benchmark baseline); it must still match dense greedy exactly."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    prompts, max_new, reqs = _mixed_requests(cfg)
    eng = ServeEngine(params, cfg, EngineConfig(
        max_slots=3, block_size=4, num_blocks=40, max_blocks_per_seq=10,
        prefill_chunk=1, prefix_sharing=False))
    out = eng.run(reqs)
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        ref = greedy_generate(params, cfg, jnp.asarray([p], jnp.int32), m)
        assert out[f"r{i}"].tokens == list(map(int, np.asarray(ref)[0, len(p):]))


def test_engine_shared_prefix_workload_hits_and_saves():
    """Requests sharing a system prompt: later admissions map cached
    blocks (prefix_hit_rate > 0, KV bytes saved), outputs still match the
    unshared engine token for token, and nothing leaks."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    sys_p = list(np.random.RandomState(7).randint(0, cfg.vocab_size, 17))
    reqs = [Request(uid=f"r{i}",
                    prompt=sys_p + list(np.random.RandomState(50 + i)
                                        .randint(0, cfg.vocab_size, 3 + i)),
                    max_new=5)
            for i in range(4)]

    def run(sharing):
        eng = ServeEngine(params, cfg, EngineConfig(
            max_slots=2, block_size=4, num_blocks=64, max_blocks_per_seq=16,
            prefix_sharing=sharing))
        out = eng.run([dataclasses.replace(r) for r in reqs])
        return eng, out

    e_on, out_on = run(True)
    e_off, out_off = run(False)
    for r in reqs:
        assert out_on[r.uid].tokens == out_off[r.uid].tokens
    s = e_on.stats()
    assert s["prefix_hit_tokens"] > 0 and s["prefix_hit_rate"] > 0
    assert s["kv_bytes_saved"] > 0
    assert s["steps"] < e_off.stats()["steps"]
    assert e_on.kv.allocator.num_free == e_on.kv.allocator.num_usable


def test_engine_warmup_compiles_both_shapes_outside_window():
    """warmup() compiles the C=1 and C=chunk steps; reset_stats() zeroes
    the energy monitor so J/token prices serving, not XLA compilation —
    and the measured run triggers no further compiles."""
    cfg = _cfg("qwen2-7b", num_kv_heads=2)
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, EngineConfig(
        max_slots=2, block_size=4, num_blocks=40, max_blocks_per_seq=10,
        prefill_chunk=4))
    eng.warmup()
    assert eng._step_fn._cache_size() == 1
    assert eng._chunk_fn._cache_size() == 1
    assert eng.monitor.total_j > 0 and "_warmup" not in eng.completions
    eng.reset_stats()
    assert eng.monitor.total_j == 0
    eng.run([Request(uid="a", prompt=list(range(1, 8)), max_new=4),
             Request(uid="b", prompt=[2, 3], max_new=3)])
    assert eng._step_fn._cache_size() == 1, "decode step recompiled"
    assert eng._chunk_fn._cache_size() == 1, "chunk step recompiled"
    s = eng.stats()
    assert s["energy_j"] > 0 and s["j_per_token"] > 0
    assert "inter_token_p99_s" in s


def test_engine_rejects_unpaged_architectures():
    cfg = tiny(get_config("mamba2-130m"))
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    assert not M.paged_decode_supported(cfg)
    with pytest.raises(NotImplementedError):
        ServeEngine(params, cfg, EngineConfig(num_blocks=8))


# --------------------------------------------------------------------------- #
# Sampling
# --------------------------------------------------------------------------- #

def test_sampler_greedy_and_topk():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray(np.random.RandomState(0).randn(4, 50), jnp.float32)
    # temperature 0 -> argmax
    out = sample_tokens(logits, key, jnp.zeros(4), jnp.zeros(4, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top-k restricts support to the k largest logits per row
    k = 3
    top = np.argsort(np.asarray(logits), axis=-1)[:, -k:]
    for s in range(20):
        out = sample_tokens(logits, jax.random.PRNGKey(s),
                            jnp.full(4, 1.0), jnp.full(4, k, jnp.int32))
        for b in range(4):
            assert int(out[b]) in top[b]


# --------------------------------------------------------------------------- #
# greedy_generate compile caching (satellite fix)
# --------------------------------------------------------------------------- #

def test_greedy_generate_reuses_jitted_step():
    cfg = _cfg("opt-125m")
    params = P.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    jitted_decode_step.cache_clear()
    greedy_generate(params, cfg, prompt, max_new=2)
    info1 = jitted_decode_step.cache_info()
    greedy_generate(params, cfg, prompt, max_new=2)
    info2 = jitted_decode_step.cache_info()
    assert info2.misses == info1.misses == 1, "step re-built per call"
    assert info2.hits > info1.hits
    step = jitted_decode_step(cfg)
    assert step._cache_size() == 1, "decode step recompiled across calls"
