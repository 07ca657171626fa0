"""Paged KV cache: kernel vs oracle, per-family parity with the contiguous
path, and engine-level admission/eviction semantics."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import CONFIGS
from repro.kernels import ref
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models.attention import (decode_attention_jnp,
                                    paged_decode_attention_jnp)
from repro.models.factory import build_model
from repro.serving.engine import InferenceEngine
from repro.serving.request import Request, chat_trace


# ------------------------------------------------------------- kernel
@pytest.mark.parametrize("b,h,kv,d,page,nb", [
    (2, 8, 4, 64, 32, 4),
    (1, 4, 1, 32, 16, 3),      # MQA, small pages
])
@pytest.mark.parametrize("rope_theta", [None, 1e4])
@pytest.mark.parametrize("layer", [None, 2])
def test_paged_kernel_matches_oracle(b, h, kv, d, page, nb, rope_theta,
                                     layer, rng_key):
    """``layer=None``: one layer's 4-D (P, KV, d, page) slab; otherwise a
    5-D pool of three layers read at ``layer``, against the oracle on that
    layer's slab."""
    num_pages = nb * b + 2
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, d))
    lead = () if layer is None else (3,)
    k_pool = jax.random.normal(ks[1], lead + (num_pages, kv, d, page))
    v_pool = jax.random.normal(ks[2], lead + (num_pages, kv, d, page))
    rng = np.random.default_rng(0)
    bt = jnp.asarray(rng.permutation(num_pages)[:b * nb].reshape(b, nb),
                     jnp.int32)
    lengths = jax.random.randint(ks[3], (b,), 1, nb * page + 1)
    lengths = lengths.astype(jnp.int32)
    if layer is None:
        out = paged_decode_attention(q, k_pool, v_pool, bt, lengths,
                                     rope_theta=rope_theta, interpret=True)
        k_pages, v_pages = k_pool, v_pool
    else:
        out = paged_decode_attention(q, k_pool, v_pool, bt, lengths,
                                     jnp.int32(layer), rope_theta=rope_theta,
                                     interpret=True)
        k_pages, v_pages = k_pool[layer], v_pool[layer]
    want = ref.paged_decode_attention_ref(q, k_pages, v_pages, bt, lengths,
                                          rope_theta=rope_theta)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_ignores_unowned_pages(rng_key):
    """Garbage in pages past `lengths` (including sentinel page 0) must not
    leak into the output — the paged analogue of the length-mask test."""
    b, h, kv, d, page, nb = 1, 4, 2, 32, 16, 4
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k_pages = jax.random.normal(ks[1], (8, kv, d, page))
    v_pages = jax.random.normal(ks[2], (8, kv, d, page))
    bt = jnp.asarray([[3, 5, 0, 0]], jnp.int32)   # tail entries = sentinel
    lengths = jnp.asarray([20], jnp.int32)        # only pages 3,5 valid
    out1 = paged_decode_attention(q, k_pages, v_pages, bt, lengths,
                                  interpret=True)
    k2 = k_pages.at[0].set(999.0).at[5, :, :, 4:].set(-999.0)
    v2 = v_pages.at[0].set(-999.0).at[5, :, :, 4:].set(999.0)
    out2 = paged_decode_attention(q, k2, v2, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_paged_jnp_fallback_matches_contiguous(rng_key):
    """With an identity block table the paged jnp lowering must reproduce
    dense decode attention exactly."""
    b, s, h, kv, d, page = 2, 64, 8, 4, 32, 16
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    nb = s // page
    k_pages = k.reshape(b * nb, page, kv, d).transpose(0, 2, 3, 1)
    v_pages = v.reshape(b * nb, page, kv, d).transpose(0, 2, 3, 1)
    bt = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    lengths = jnp.asarray([37, 64], jnp.int32)
    got = paged_decode_attention_jnp(q, k_pages, v_pages, bt, lengths,
                                     rope_theta=1e4)
    want = decode_attention_jnp(q, k, v, lengths, rope_theta=1e4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ------------------------------------------------- per-family parity
PAGED_ARCHS = ["tinyllama-1.1b", "jamba-v0.1-52b", "moonshot-v1-16b-a3b",
               "seamless-m4t-large-v2"]


def _family_model(arch, rng_key):
    cfg = CONFIGS[arch].reduced()
    if cfg.family != "hybrid":   # hybrid: keep one full period
        cfg = dataclasses.replace(cfg, num_layers=min(cfg.num_layers, 2))
    if cfg.is_moe:               # avoid capacity-drop mismatch across paths
        cfg = dataclasses.replace(cfg, capacity_factor=4.0)
    m = build_model(cfg)
    return m, m.init(rng_key), cfg


@pytest.mark.parametrize("programs", ["eager", "donated"])
@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_decode_token_identical_per_family(arch, programs, rng_key):
    """The tentpole parity pin: chunked prefill + greedy decode through the
    PAGED cache produces the same logits (tight tolerance) and the same
    argmax tokens as the contiguous cache, for every family with KV.
    ``donated``: through the engine's jitted programs, each of which
    donates the cache it is handed."""
    m, params, cfg = _family_model(arch, rng_key)
    assert m.cache_pages()
    b, plen, max_seq, page = 2, 13, 32, 8
    if programs == "donated":
        eng = InferenceEngine(m, max_slots=b, max_seq=max_seq, page_size=page)
        prefill_c, prefill_p = eng._jit_prefill, eng._jit_prefill_paged
        decode_c, decode_p = eng._jit_decode, eng._jit_decode_paged
    else:
        prefill_c, prefill_p = m.prefill_chunk, m.prefill_chunk_paged
        decode_c, decode_p = m.decode_step, m.decode_step_paged
    toks = jax.random.randint(rng_key, (b, plen), 0, cfg.vocab_size)
    cache_c = m.init_cache(b, max_seq)
    cache_p = m.init_paged_cache(8, page, b, max_seq)
    bt = jnp.asarray(np.arange(8, dtype=np.int32).reshape(b, 4))
    start = jnp.zeros((b,), jnp.int32)
    for lo in range(0, plen, 5):         # chunk 5: non-divisible tail
        hi = min(plen, lo + 5)
        lc, cache_c = prefill_c(params, cache_c, toks[:, lo:hi], start)
        lp, cache_p = prefill_p(params, cache_p, toks[:, lo:hi], start, bt)
        start = start + (hi - lo)
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(lc, np.float32),
                               atol=1e-4, rtol=1e-4)
    ln = jnp.full((b,), plen, jnp.int32)
    tok = toks[:, -1:]
    for _ in range(4):
        dc, cache_c = decode_c(params, cache_c, tok, ln)
        dp, cache_p = decode_p(params, cache_p, tok, ln, bt)
        np.testing.assert_allclose(np.asarray(dp, np.float32),
                                   np.asarray(dc, np.float32),
                                   atol=1e-4, rtol=1e-4)
        want = np.asarray(jnp.argmax(dc, -1))
        got = np.asarray(jnp.argmax(dp, -1))
        # token-identical wherever the argmax is numerically decided (the
        # logits already matched to 1e-4 above)
        top2 = np.sort(np.asarray(dc, np.float32), axis=-1)[:, -2:]
        decided = (top2[:, 1] - top2[:, 0]) > 1e-3
        np.testing.assert_array_equal(got[decided], want[decided])
        tok = (want[:, None] % cfg.vocab_size).astype(np.int32)
        ln = ln + 1


def test_ssm_family_has_no_pages(rng_key):
    cfg = dataclasses.replace(CONFIGS["mamba2-1.3b"].reduced(), num_layers=2)
    m = build_model(cfg)
    assert not m.cache_pages()
    with pytest.raises(ValueError, match="ssm"):
        m.init_paged_cache(4, 8, 1, 32)
    with pytest.raises(ValueError, match="cannot page"):
        InferenceEngine(m, max_slots=2, max_seq=32, paged=True)
    eng = InferenceEngine(m, max_slots=2, max_seq=32)
    assert not eng.paged                 # auto-resolves to contiguous


# ---------------------------------------------------------- engine
@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(CONFIGS["tinyllama-1.1b"].reduced(),
                              num_layers=2)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    return m, params, cfg


def _run_engine(m, params, cfg, *, paged, n=3, max_new=5, **kw):
    eng = InferenceEngine(m, max_slots=2, max_seq=64, policy="chunked",
                          prefill_chunk=4, paged=paged, **kw)
    eng.load_params(params)
    for r in chat_trace(n, cfg.vocab_size, mean_prompt=10, max_new=max_new):
        eng.submit(r)
    done = {r.request_id: r.tokens_out for r in eng.run()}
    assert len(done) == n
    return done, eng.stats


def test_engine_paged_is_default_and_token_identical(tiny_model):
    m, params, cfg = tiny_model
    eng = InferenceEngine(m, max_slots=2, max_seq=64)
    assert eng.paged                     # paged is the engine default now
    want, _ = _run_engine(m, params, cfg, paged=False)
    got, stats = _run_engine(m, params, cfg, paged=True, page_size=8)
    assert got == want
    assert stats.pages_in_use > 0
    assert stats.evictions == 0          # default-ish pool: no pressure


def test_step_programs_donate_the_cache(tiny_model):
    """The engine's step programs update the cache where it lies: each
    prefill and decode dispatch deletes the cache it was handed (the new
    one took over its buffers), and the compiled decode program aliases
    both pool leaves in to out."""
    m, params, cfg = tiny_model
    eng = InferenceEngine(m, max_slots=2, max_seq=64, policy="chunked",
                          prefill_chunk=4, page_size=8)
    eng.load_params(params)
    eng.submit(Request(0, np.arange(1, 7, dtype=np.int32), 3,
                       arrival_s=0.0))
    st = eng.stats
    while not (st.prefill_dispatches and st.decode_syncs):
        held = jax.tree.leaves(eng.cache)
        dispatched = st.prefill_dispatches + st.decode_syncs
        eng.step()
        assert st.prefill_dispatches + st.decode_syncs > dispatched
        assert all(leaf.is_deleted() for leaf in held)
    b = eng.max_slots
    text = eng._jit_decode_paged.lower(
        params, eng.cache, jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.asarray(eng.allocator.tables),
        jnp.ones((b,), bool)).compile().as_text()
    header = text.splitlines()[0]
    aliased = {int(i) for i in re.findall(r"\((\d+), \{\}, \w+-alias\)",
                                          header)}
    pool = len(jax.tree.leaves(params))      # the cache follows the params
    assert {pool, pool + 1} <= aliased, header[:300]


def test_engine_eviction_recompute_stays_token_identical(tiny_model):
    """A pool too small for all slots forces preempt-to-evict; the evicted
    request's re-prefill must replay its exact cache, so the final token
    streams STILL match the contiguous engine."""
    m, params, cfg = tiny_model
    want, _ = _run_engine(m, params, cfg, paged=False)
    got, stats = _run_engine(m, params, cfg, paged=True, page_size=4,
                             kv_pages=8)
    assert got == want
    assert stats.evictions > 0
    assert stats.recompute_tokens > 0
    assert stats.pages_in_use <= 8


def test_engine_watermark_eviction(tiny_model):
    m, params, cfg = tiny_model
    want, _ = _run_engine(m, params, cfg, paged=False)
    got, stats = _run_engine(m, params, cfg, paged=True, page_size=4,
                             kv_pages=12, evict_high_watermark=0.75,
                             evict_low_watermark=0.5)
    assert got == want
    assert stats.evictions > 0
    # watermark policy keeps peak below the hard pool size
    assert stats.pages_in_use <= 12


def test_oom_admission_contiguous_refuses_paged_admits(tiny_model):
    """The acceptance pin: under a page budget smaller than the contiguous
    reservation, the contiguous engine refuses at construction while the
    paged engine admits the workload (whose aggregate KV demand exceeds
    the pool) and completes it via eviction."""
    m, params, cfg = tiny_model
    with pytest.raises(ValueError, match="reserves max_slots x max_seq"):
        InferenceEngine(m, max_slots=4, max_seq=64, paged=False,
                        kv_pages=8, page_size=8)
    eng = InferenceEngine(m, max_slots=4, max_seq=64, paged=True,
                          kv_pages=8, page_size=8, policy="chunked",
                          prefill_chunk=4)
    eng.load_params(params)
    rng = np.random.default_rng(0)
    total_demand = 0
    for i in range(4):
        prompt = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
        eng.submit(Request(i, prompt, 10, arrival_s=0.0))
        total_demand += len(prompt) + 10
    assert total_demand > 8 * 8          # demand exceeds the whole pool
    done = eng.run()
    assert len(done) == 4
    assert all(len(r.tokens_out) == 10 for r in done)
    assert eng.stats.pages_in_use <= 8


def test_oversized_request_fails_loudly(tiny_model):
    m, params, cfg = tiny_model
    eng = InferenceEngine(m, max_slots=2, max_seq=64, paged=True,
                          kv_pages=2, page_size=4)   # pool: 8 tokens
    eng.load_params(params)
    eng.submit(Request(0, np.arange(30, dtype=np.int32) % cfg.vocab_size,
                       4, arrival_s=0.0))
    with pytest.raises(RuntimeError, match="never be admitted"):
        eng.run()


def test_memory_aware_admission_lets_small_requests_flow(tiny_model):
    """Page-gated admission skips a request that does not fit but admits a
    later smaller one — slots no longer imply worst-case memory."""
    m, params, cfg = tiny_model
    eng = InferenceEngine(m, max_slots=2, max_seq=64, paged=True,
                          kv_pages=10, page_size=4, policy="fcfs",
                          prefill_chunk=4)
    eng.load_params(params)
    rng = np.random.default_rng(1)
    big = Request(0, rng.integers(0, cfg.vocab_size, 30).astype(np.int32),
                  4, arrival_s=0.0)
    small = Request(1, rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                    2, arrival_s=0.0)
    eng.submit(big)
    eng.submit(small)
    eng.step()                            # big admits (8 pages), small waits
    assert eng.active[0] is big
    eng.submit(Request(2, rng.integers(0, cfg.vocab_size, 4)
                       .astype(np.int32), 2, arrival_s=0.0))
    eng.step()                            # 2 free pages: small (2 pages) fits
    assert small in eng.active
    done = eng.run()
    assert {r.request_id for r in done} == {0, 1, 2}
