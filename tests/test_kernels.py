"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels import paged_mla_prefill_attention as mla_prefill_mod
from repro.kernels.paged_mla_decode_attention import \
    paged_mla_decode_attention
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.kernels.prefill_attention import prefill_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_chunk_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 8, 4, 256, 64),     # GQA 2:1
    (1, 8, 2, 128, 128),    # GQA 4:1, wide head
    (2, 4, 1, 256, 32),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(b, h, kv, s, d, dtype, causal, rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("b,h,kv,s,d", [
    (2, 8, 4, 256, 64),
    (1, 4, 4, 512, 32),
    (3, 8, 2, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, kv, s, d, dtype, rng_key):
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1).astype(jnp.int32)
    out = decode_attention(q, k, v, lengths, s_block=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_respects_length(rng_key):
    """Tokens beyond `lengths` must not affect the output."""
    b, h, kv, s, d = 1, 4, 2, 128, 32
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    lengths = jnp.array([40], jnp.int32)
    out1 = decode_attention(q, k, v, lengths, s_block=32, interpret=True)
    k2 = k.at[:, :, 40:].set(999.0)
    v2 = v.at[:, :, 40:].set(-999.0)
    out2 = decode_attention(q, k2, v2, lengths, s_block=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


@pytest.mark.parametrize("m,q,h,p,n,hb", [
    (2, 64, 16, 32, 64, 8),
    (1, 32, 8, 64, 32, 4),
    (4, 128, 4, 16, 128, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_scan(m, q, h, p, n, hb, dtype, rng_key):
    ks = jax.random.split(rng_key, 4)
    x = jax.random.normal(ks[0], (m, q, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (m, q, h))).astype(jnp.float32)
    cum = jnp.cumsum(-0.1 * dt, axis=1)
    b_ = jax.random.normal(ks[2], (m, q, n), dtype)
    c_ = jax.random.normal(ks[3], (m, q, n), dtype)
    y, st = ssd_chunk_scan(x, dt, cum, b_, c_, head_block=hb, interpret=True)
    y_ref, st_ref = jax.vmap(ref.ssd_chunk_ref)(x, dt, cum, b_, c_)
    tol = 20 * _tol(dtype)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("r,d", [(256, 128), (64, 512), (512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(r, d, dtype, rng_key):
    ks = jax.random.split(rng_key, 2)
    x = jax.random.normal(ks[0], (r, d), dtype)
    w = jax.random.normal(ks[1], (d,), jnp.float32)
    out = rmsnorm(x, w, row_block=64, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_non_divisible_seq(rng_key):
    """S not divisible by s_block: pad+mask fallback instead of assert."""
    b, h, kv, s, d = 2, 8, 4, 130, 64
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1).astype(jnp.int32)
    out = decode_attention(q, k, v, lengths, s_block=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_fused_rope(rng_key):
    """Fused-RoPE decode == rope(q at lengths-1) then plain attention, for
    kernel (interpret), jnp lowering, and ref oracle alike."""
    from repro.models.attention import decode_attention_jnp
    from repro.models.layers import apply_rope
    b, h, kv, s, d = 2, 8, 4, 128, 64
    theta = 10_000.0
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1).astype(jnp.int32)
    # manual: rotate q at the new token's position, then un-fused attention
    q_rot = apply_rope(q[:, None], (lengths - 1)[:, None], theta)[:, 0]
    want = ref.decode_attention_ref(q_rot, k, v, lengths)
    got_kernel = decode_attention(q, k, v, lengths, s_block=64,
                                  rope_theta=theta, interpret=True)
    got_ref = ref.decode_attention_ref(q, k, v, lengths, rope_theta=theta)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # model-facing jnp lowering: (B,1,H,d) against (B,S,KV,d) caches
    got_jnp = decode_attention_jnp(
        q[:, None], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        lengths, rope_theta=theta)[:, 0]
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_ssd_chunk_scan_non_divisible_heads(rng_key):
    """H not divisible by head_block: largest-divisor fallback."""
    m, q, h, p, n = 2, 32, 6, 16, 32
    ks = jax.random.split(rng_key, 4)
    x = jax.random.normal(ks[0], (m, q, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (m, q, h)))
    cum = jnp.cumsum(-0.1 * dt, axis=1)
    b_ = jax.random.normal(ks[2], (m, q, n))
    c_ = jax.random.normal(ks[3], (m, q, n))
    y, st = ssd_chunk_scan(x, dt, cum, b_, c_, head_block=4, interpret=True)
    y_ref, st_ref = jax.vmap(ref.ssd_chunk_ref)(x, dt, cum, b_, c_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=4e-4, rtol=4e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=4e-4, rtol=4e-4)


def test_flash_attention_non_divisible_seq(rng_key):
    """Sq/Skv not divisible by the blocks: largest-divisor fallback."""
    b, h, kv, s, d = 1, 4, 2, 96, 32
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    out = flash_attention(q, k, v, causal=True, q_block=64, kv_block=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_autotuned_blocks_match_oracle(rng_key, tmp_path, monkeypatch):
    """Entry points called WITHOUT explicit blocks consult the autotuner and
    still match the jnp oracles (interpret mode)."""
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune.reset()
    try:
        ks = jax.random.split(rng_key, 4)
        b, h, kv, s, d = 2, 8, 4, 192, 64
        q = jax.random.normal(ks[0], (b, h, d))
        k = jax.random.normal(ks[1], (b, kv, s, d))
        v = jax.random.normal(ks[2], (b, kv, s, d))
        lengths = jax.random.randint(ks[3], (b,), 1, s + 1).astype(jnp.int32)
        out = decode_attention(q, k, v, lengths, interpret=True)
        want = ref.decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        qf = jax.random.normal(ks[0], (1, 4, 128, 32))
        kf = jax.random.normal(ks[1], (1, 2, 128, 32))
        vf = jax.random.normal(ks[2], (1, 2, 128, 32))
        of = flash_attention(qf, kf, vf, causal=True, interpret=True)
        np.testing.assert_allclose(
            np.asarray(of),
            np.asarray(ref.flash_attention_ref(qf, kf, vf, causal=True)),
            atol=2e-5, rtol=2e-5)
        assert (tmp_path / "autotune.json").exists()  # persisted
    finally:
        autotune.reset()


@pytest.mark.parametrize("b,h,kv,c,s,d", [
    (1, 4, 4, 8, 128, 64),     # MHA
    (2, 8, 4, 4, 256, 64),     # GQA 2:1
    (1, 8, 2, 16, 128, 32),    # GQA 4:1
    (2, 4, 1, 8, 128, 32),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_attention(b, h, kv, c, s, d, dtype, rng_key):
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, c, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    start = jax.random.randint(ks[3], (b,), 0, s - c + 1).astype(jnp.int32)
    out = prefill_attention(q, k, v, start, s_block=64, interpret=True)
    want = ref.prefill_attention_ref(q, k, v, start)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_prefill_attention_respects_horizon(rng_key):
    """Cache positions beyond each row's causal horizon must not affect
    the chunk's output (that is what makes pad-to-widest multi-slot
    batching sound)."""
    b, h, kv, c, s, d = 2, 4, 2, 8, 128, 32
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, h, c, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    start = jnp.array([16, 40], jnp.int32)
    out1 = prefill_attention(q, k, v, start, s_block=32, interpret=True)
    # poison everything past the last chunk token's horizon, per row
    horizon = np.asarray(start) + c
    k2, v2 = np.asarray(k).copy(), np.asarray(v).copy()
    for i in range(b):
        k2[i, :, horizon[i]:] = 999.0
        v2[i, :, horizon[i]:] = -999.0
    out2 = prefill_attention(q, jnp.asarray(k2), jnp.asarray(v2), start,
                             s_block=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_prefill_attention_non_divisible_seq(rng_key):
    """S not divisible by s_block: pad+mask fallback instead of assert."""
    b, h, kv, c, s, d = 2, 8, 4, 4, 130, 64
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, c, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    start = jax.random.randint(ks[3], (b,), 0, s - c + 1).astype(jnp.int32)
    out = prefill_attention(q, k, v, start, s_block=64, interpret=True)
    want = ref.prefill_attention_ref(q, k, v, start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_prefill_attention_fused_rope(rng_key):
    """Fused-RoPE prefill == rope(q at start+j) then plain attention, for
    kernel (interpret), jnp lowering, and ref oracle alike."""
    from repro.models.attention import prefill_chunk_attention_jnp
    b, h, kv, c, s, d = 2, 8, 4, 8, 128, 64
    theta = 10_000.0
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (b, h, c, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    start = jax.random.randint(ks[3], (b,), 0, s - c + 1).astype(jnp.int32)
    positions = start[:, None] + jnp.arange(c)                  # (B, C)
    q_rot = ref.rope_ref(q, positions[:, None, :], theta).astype(q.dtype)
    want = ref.prefill_attention_ref(q_rot, k, v, start)
    got_kernel = prefill_attention(q, k, v, start, s_block=64,
                                   rope_theta=theta, interpret=True)
    got_ref = ref.prefill_attention_ref(q, k, v, start, rope_theta=theta)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # model-facing jnp lowering: (B,C,H,d) against (B,S,KV,d) caches
    got_jnp = prefill_chunk_attention_jnp(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), positions,
        rope_theta=theta).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,h,kv,c,d,page,nb,pool", [
    (2, 8, 4, 4, 64, 16, 8, 24),
    (1, 4, 1, 8, 32, 8, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layer", [None, 1])
def test_paged_prefill_attention(b, h, kv, c, d, page, nb, pool, dtype,
                                 layer, rng_key):
    """``layer=None``: one layer's 4-D (P, KV, d, page) slab; otherwise a
    5-D pool of two layers read at ``layer``, against the oracle on that
    layer's slab."""
    ks = jax.random.split(rng_key, 5)
    q = jax.random.normal(ks[0], (b, h, c, d), dtype)
    lead = () if layer is None else (2,)
    k_pool = jax.random.normal(ks[1], lead + (pool, kv, d, page), dtype)
    v_pool = jax.random.normal(ks[2], lead + (pool, kv, d, page), dtype)
    tables = jax.random.randint(ks[3], (b, nb), 0, pool).astype(jnp.int32)
    s = nb * page
    start = jax.random.randint(ks[4], (b,), 0, s - c + 1).astype(jnp.int32)
    if layer is None:
        out = paged_prefill_attention(q, k_pool, v_pool, tables, start,
                                      interpret=True)
        k_pages, v_pages = k_pool, v_pool
    else:
        out = paged_prefill_attention(q, k_pool, v_pool, tables, start,
                                      jnp.int32(layer), interpret=True)
        k_pages, v_pages = k_pool[layer], v_pool[layer]
    want = ref.paged_prefill_attention_ref(q, k_pages, v_pages, tables,
                                           start)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_paged_prefill_attention_fused_rope(rng_key):
    """Fused-RoPE paged prefill: kernel == paged oracle == dense oracle on
    the gathered view."""
    b, h, kv, c, d, page, nb, pool = 2, 8, 4, 8, 64, 16, 8, 24
    theta = 10_000.0
    ks = jax.random.split(rng_key, 5)
    q = jax.random.normal(ks[0], (b, h, c, d))
    k_pages = jax.random.normal(ks[1], (pool, kv, d, page))
    v_pages = jax.random.normal(ks[2], (pool, kv, d, page))
    tables = jax.random.randint(ks[3], (b, nb), 0, pool).astype(jnp.int32)
    s = nb * page
    start = jax.random.randint(ks[4], (b,), 0, s - c + 1).astype(jnp.int32)
    got = paged_prefill_attention(q, k_pages, v_pages, tables, start,
                                  rope_theta=theta, interpret=True)
    want = ref.paged_prefill_attention_ref(q, k_pages, v_pages, tables,
                                           start, rope_theta=theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # dense-oracle cross-check on the gathered view
    kd = k_pages[tables].transpose(0, 2, 1, 4, 3).reshape(b, kv, s, d)
    vd = v_pages[tables].transpose(0, 2, 1, 4, 3).reshape(b, kv, s, d)
    dense = ref.prefill_attention_ref(q, kd, vd, start, rope_theta=theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_ops_interpret_backend_end_to_end(rng_key):
    """Whole model under the interpret backend == jnp backend."""
    from repro.configs.registry import CONFIGS
    from repro.kernels import ops
    from repro.models.factory import build_model
    cfg = CONFIGS["tinyllama-1.1b"].reduced()
    m = build_model(cfg)
    params = m.init(rng_key)
    toks = jax.random.randint(rng_key, (2, 64), 0, cfg.vocab_size)
    try:
        ops.set_backend("jnp")
        l1, _ = m.forward(params, {"tokens": toks})
        ops.set_backend("interpret")
        l2, _ = m.forward(params, {"tokens": toks})
    finally:
        ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=5e-5)


# ------------------------------------------------- paged latent attention
#: (heads, latent rank, rotary width, page, blocks, pool pages)
MLA_SHAPES = [(4, 32, 8, 8, 4, 12), (16, 64, 16, 16, 3, 10)]


def _latent_pool(key, shape, layer):
    h, r, rp, page, nb, pool = shape
    lead = () if layer is None else (2,)
    return jax.random.normal(key, lead + (pool, 1, r + rp, page),
                             jnp.bfloat16)


@pytest.mark.parametrize("shape", MLA_SHAPES)
@pytest.mark.parametrize("layer", [None, 1])
def test_paged_mla_decode_attention(shape, layer, rng_key):
    """The latent decode kernel (interpret) against its oracle, lengths
    ending inside a page, on a page boundary and at one token; a 5-D pool
    read at ``layer``."""
    h, r, rp, page, nb, pool = shape
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (4, h, r + rp))
    pages = _latent_pool(ks[1], shape, layer)
    tables = jax.random.permutation(ks[2], pool)[:4 * nb].reshape(4, nb) \
        if pool >= 4 * nb else jax.random.randint(ks[2], (4, nb), 0, pool)
    tables = tables.astype(jnp.int32)
    lengths = jnp.asarray([1, page, page + 3, nb * page - 1], jnp.int32)
    scale = 1.0 / np.sqrt(2 * rp + r)
    out = paged_mla_decode_attention(
        q, pages, tables, lengths, 0 if layer is None else layer,
        scale=scale, rank=r, interpret=True)
    slab = pages if layer is None else pages[layer]
    want = ref.paged_mla_decode_attention_ref(q, slab, tables, lengths,
                                              scale=scale, rank=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", MLA_SHAPES)
@pytest.mark.parametrize("c,tile", [(5, None), (6, 8), (3, 4)])
def test_paged_mla_prefill_attention(shape, c, tile, rng_key, monkeypatch):
    """The latent prefill kernel (interpret) against its oracle: chunks
    starting inside a page and crossing into the next, several row tiles
    with a padded last one (``tile``) and a single whole tile."""
    h, r, rp, page, nb, pool = shape
    if tile is not None:
        monkeypatch.setattr(mla_prefill_mod, "ROW_TILE", tile)
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (3, c * h, r + rp))
    pages = _latent_pool(ks[1], shape, None)
    tables = jax.random.randint(ks[2], (3, nb), 0, pool).astype(jnp.int32)
    start = jnp.asarray([0, page - 2, nb * page - c], jnp.int32)
    scale = 1.0 / np.sqrt(2 * rp + r)
    kernel = mla_prefill_mod.paged_mla_prefill_attention.__wrapped__
    out = kernel(q, pages, tables, start, heads=h, scale=scale, rank=r,
                 interpret=True)
    want = ref.paged_mla_prefill_attention_ref(q, pages, tables, start,
                                               heads=h, scale=scale, rank=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_latent_model_interpret_backend_matches_jnp(rng_key):
    """A latent-attention expert model's paged prefill and decode under the
    interpret backend (the two latent kernels) == the jnp backend."""
    import dataclasses

    from repro.configs.registry import CONFIGS
    from repro.kernels import ops
    from repro.models.factory import build_model
    cfg = dataclasses.replace(CONFIGS["moonshot-v1-16b-a3b"].reduced(),
                              num_layers=2)
    m = build_model(cfg)
    params = m.init(rng_key)
    toks = jax.random.randint(rng_key, (2, 11), 0, cfg.vocab_size)
    bt = jnp.asarray(np.arange(1, 7, dtype=np.int32).reshape(2, 3))
    out = {}
    try:
        for be in ("jnp", "interpret"):
            ops.set_backend(be)
            cache = m.init_paged_cache(7, 8, 2, 24)
            lp, cache = m.prefill_chunk_paged(
                params, cache, toks, jnp.zeros((2,), jnp.int32), bt)
            ld, _ = m.decode_step_paged(params, cache, toks[:, -1:],
                                        jnp.full((2,), 11, jnp.int32), bt)
            out[be] = (np.asarray(lp, np.float32), np.asarray(ld, np.float32))
    finally:
        ops.set_backend(None)
    for a, b in zip(out["jnp"], out["interpret"]):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
