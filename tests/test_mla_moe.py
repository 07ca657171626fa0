"""Latent attention and the held-share dropless expert layer (the
Moonlight-16B-A3B block) at a tiny size on seeded random weights, against
the plain float32 reference (``repro.models.reference_mla_moe``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench.policy import MixedBatchPolicy
from repro.configs.registry import CONFIGS
from repro.models import layers, mla, moe, reference_mla_moe as ref
from repro.models.factory import build_model
from repro.serving.engine import InferenceEngine
from repro.serving.request import Request

#: 3 layers (one dense, two of 8 experts, top 2), the first 4 held
TINY = dataclasses.replace(CONFIGS["moonshot-v1-16b-a3b"].reduced(),
                           num_layers=3, num_experts=8, experts_held=4)


def _params(model, key, dtype=jnp.float32):
    """Seeded weights: matrices from the program's initialiser, and the
    leaves it sets to constants (norm scales, the correction bias) drawn
    too, so that a path that ignored one would show. Leaves the program
    serves in bf16 are rounded to ``dtype``; the router stays float32."""
    p = model.init(key, jnp.float32)
    flat, tree = jax.tree_util.tree_flatten_with_path(p)
    want = jax.tree.leaves(model.abstract_params(jnp.bfloat16))
    out = []
    for i, ((path, leaf), spec) in enumerate(zip(flat, want)):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if "norm" in name or name.endswith("['ln1']") or \
                name.endswith("['ln2']") or name.endswith("['ln_f']"):
            leaf = 1.0 + 0.1 * jax.random.normal(k, leaf.shape)
        elif "router_bias" in name:
            leaf = 0.5 * jax.random.normal(k, leaf.shape)
        elif "router" in name:
            leaf = 0.3 * jax.random.normal(k, leaf.shape)
        if spec.dtype == jnp.bfloat16:
            leaf = leaf.astype(dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def _served(eng, reqs):
    """Runs the engine, recording each decode dispatch's logits per
    request: ``{request_id: [(position, logits)]}``."""
    seen: dict = {}
    decode = eng._jit_decode_paged

    def record(p, c, t, ln, bt, act):
        holders = [None if r is None else r.request_id for r in eng.active]
        logits, c = decode(p, c, t, ln, bt, act)
        got = np.asarray(logits, np.float32)
        for s in np.flatnonzero(np.asarray(act)):
            seen.setdefault(holders[s], []).append((int(ln[s]), got[s]))
        return logits, c

    eng._jit_decode_paged = record
    for r in reqs:
        eng.submit(r)
    eng.run()
    return seen


#: of the largest logit: the pool holds the latent rows in bf16 (relative
#: rounding 2**-9), which with float32 weights moves the logits by 1.7e-3
#: here; with bf16 weights and activations every product rounds too, and
#: they move by 6.5e-3, which this bound must catch
LOGIT_TOL = 3.5e-3


@pytest.mark.parametrize("dtype,within", [("float32", True),
                                          ("bfloat16", False)])
def test_engine_prefill_then_decode_matches_the_reference(dtype, within):
    """Paged multi-slot chunked prefill, then decode through the latent
    pool, served by the engine, against the reference's full forward pass
    over what each request was fed."""
    model = build_model(TINY)
    params = _params(model, jax.random.key(0), getattr(jnp, dtype))
    eng = InferenceEngine(model, max_slots=3, max_seq=40, page_size=8,
                          prefill_chunk=4,
                          policy=MixedBatchPolicy(prefill_share=0.5))
    eng.load_params(params)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, TINY.vocab_size, n).astype(np.int32),
                    4) for i, n in enumerate((13, 9, 6))]
    seen = _served(eng, reqs)
    assert eng.stats.prefill_dispatches < sum(len(r.prompt) for r in reqs)
    worst = 0.0
    for r in reqs:
        # the first decode step feeds the prompt's last token again
        fed = np.concatenate([r.prompt, r.prompt[-1:], r.tokens_out[:-1]])
        want = ref.logits(params, fed[None], TINY)[0]
        for pos, got in seen[r.request_id]:
            scale = np.abs(want[pos]).max()
            worst = max(worst, np.abs(got[:TINY.vocab_size]
                                      - want[pos]).max() / scale)
    assert (worst < LOGIT_TOL) == within, worst


@pytest.mark.parametrize("length", [1, 11])
def test_absorbed_attention_matches_the_textbook_form(length):
    """The program's absorbed latent attention (queries through the key
    half of ``wkv_b``, values back through its value half) against the
    reference's per-head keys and values."""
    params = _params(build_model(TINY), jax.random.key(1))
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    x = jax.random.normal(jax.random.key(2), (2, length, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        got, _ = mla.attention_block(p, x, TINY, jnp.arange(length)[None])
        want = ref.attention(p, x, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.abs(want).max()))


@pytest.mark.parametrize("held", [2, 4])
def test_the_expert_shares_add_up_to_the_whole_layer(held):
    """Guide section 4: each chip's share of the routed experts, with the
    shared experts (which every chip computes alike) counted once, adds up
    to the uncut layer; and the uncut layer is the reference's."""
    whole = dataclasses.replace(TINY, experts_held=0)
    p = _params(build_model(whole), jax.random.key(4))
    p = jax.tree.map(lambda a: a[0], p["layers"]["ffn"])
    x = jax.random.normal(jax.random.key(5), (2, 7, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        y_whole, _, c_whole = moe.moe_held(p, x, whole)
        shared = layers.mlp(p["shared"], x)
        total = -(whole.num_experts // held - 1) * shared
        held_picks = 0
        for first in range(0, whole.num_experts, held):
            cfg = dataclasses.replace(whole, experts_held=held)
            share = dict(p, **{n: p[n][first:first + held]
                               for n in ("w_gate", "w_up", "w_down")})
            y, _, c = moe.moe_held(share, x, cfg, first=first)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(ref.experts(share, x, cfg, first)),
                atol=1e-5 * float(jnp.abs(y).max()))
            total = total + y
            held_picks += int(c[1])
        want = ref.experts(p, x, whole)
    scale = float(jnp.abs(y_whole).max())
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole),
                               atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(y_whole), np.asarray(want),
                               atol=1e-5 * scale)
    assert held_picks == int(c_whole[1]) == int(c_whole[0])


@pytest.mark.parametrize("counts", [(5, 5, 5), (5, 3, 2)])
def test_a_rows_output_does_not_depend_on_its_batch_mates(counts):
    """Multi-slot paged prefill (one dispatch, per-row ``valid`` counts) is
    bit-identical to one dispatch per row at the same width: the dropless
    expert layer couples no rows."""
    model = build_model(TINY)
    params = _params(model, jax.random.key(6))
    b, width, page = 3, 5, 4
    toks = jax.random.randint(jax.random.key(7), (b, width), 0,
                              TINY.vocab_size)
    bt = jnp.asarray(np.arange(1, 1 + 2 * b, dtype=np.int32).reshape(b, 2))
    start = jnp.zeros((b,), jnp.int32)
    valid = jnp.asarray(counts, jnp.int32)
    pool = model.init_paged_cache(2 * b + 1, page, b, 8)
    lb, pool_b = model.prefill_chunk_paged(params, pool, toks, start, bt,
                                           jnp.ones((b,), bool), valid)
    pool_s = model.init_paged_cache(2 * b + 1, page, b, 8)
    for i in range(b):
        li, pool_s = model.prefill_chunk_paged(
            params, pool_s, toks, start, bt, jnp.arange(b) == i, valid)
        np.testing.assert_array_equal(np.asarray(lb[i, :counts[i]]),
                                      np.asarray(li[i, :counts[i]]))
    np.testing.assert_array_equal(np.asarray(pool_b["latent_pages"]),
                                  np.asarray(pool_s["latent_pages"]))


def test_multi_slot_serving_streams_equal_per_slot_serving():
    """Through the engine: the budgeted multi-slot prefill serves the same
    tokens as one slot per prefill dispatch, with fewer dispatches."""
    model = build_model(TINY)
    params = _params(model, jax.random.key(8))
    out = {}
    for policy in ("chunked", MixedBatchPolicy(prefill_share=0.5)):
        eng = InferenceEngine(model, max_slots=3, max_seq=40, page_size=8,
                              prefill_chunk=4, policy=policy)
        eng.load_params(params)
        rng = np.random.default_rng(9)
        for i, n in enumerate((11, 7, 5, 9)):
            eng.submit(Request(i, rng.integers(0, TINY.vocab_size, n)
                               .astype(np.int32), 5))
        out[str(policy)] = ({r.request_id: r.tokens_out for r in eng.run()},
                            eng.stats.prefill_dispatches)
    (a, da), (b_, db) = out.values()
    assert a == b_
    assert db < da
    assert model.prefix_shareable()


def test_layer_zero_is_dense_and_the_rest_hold_experts():
    model = build_model(TINY)
    p = model.abstract_params(jnp.bfloat16)
    dense, routed = p["dense_layers"], p["layers"]
    assert dense["ln1"].shape[0] == TINY.first_k_dense_replace == 1
    assert routed["ln1"].shape[0] == TINY.num_layers - 1
    assert set(dense["ffn"]) == {"w_gate", "w_up", "w_down"}
    assert dense["ffn"]["w_gate"].shape == (1, TINY.d_model, TINY.d_ff)
    assert routed["ffn"]["router"].shape[-1] == TINY.num_experts
    assert routed["ffn"]["router_bias"].shape[-1] == TINY.num_experts
    assert routed["ffn"]["w_gate"].shape[:2] == (2, TINY.experts_held)
    assert set(dense["attn"]) == set(routed["attn"]) == {
        "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    cache = model.init_paged_cache(5, 8, 2, 16)
    assert cache["latent_pages"].shape == (3, 5, 1, TINY.latent_width, 8)
    # the pool is sized by the bytes one token costs in all layers
    per_token = model.kv_bytes_per_token()
    assert per_token == TINY.num_layers * TINY.latent_width * 2
    assert cache["latent_pages"].nbytes == 5 * 8 * per_token
    assert build_model(CONFIGS["moonshot-v1-16b-a3b"]).kv_bytes_per_token() \
        == 31_104


def _hand_count(params, toks, live, cfg):
    """(picks, held picks, busiest held expert's picks summed over layers)
    of the live tokens, routed through the reference layer by layer."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    picks = held = peak = 0
    with jax.default_matmul_precision("highest"):
        x = p["embedding"][toks]
        for lp, routed in ref._layers(p):
            x = x + ref.attention(lp["attn"], ref._rms(x, lp["ln1"],
                                                       cfg.norm_eps), cfg)
            m = ref._rms(x, lp["ln2"], cfg.norm_eps)
            if routed:
                ids, _ = ref.route(lp["ffn"], m.reshape(-1, cfg.d_model),
                                   cfg)
                ids = np.asarray(ids)[np.asarray(live).reshape(-1)]
                mine = ids[ids < cfg.experts_held]
                picks += ids.size
                held += mine.size
                peak += np.bincount(mine, minlength=cfg.experts_held).max()
                x = x + ref.experts(lp["ffn"], m, cfg)
            else:
                x = x + ref._swiglu(lp["ffn"], m)
    return picks, held, peak


def test_the_routing_counters_match_a_count_by_hand():
    """One paged prefill dispatch with one row masked out and one short
    row: the counters the step program sums on the device equal a count
    from the reference's routing of the live tokens."""
    model = build_model(TINY)
    params = _params(model, jax.random.key(10))
    b, width = 3, 6
    toks = jax.random.randint(jax.random.key(11), (b, width), 0,
                              TINY.vocab_size)
    active = jnp.asarray([True, False, True])
    valid = jnp.asarray([6, 6, 4], jnp.int32)
    bt = jnp.asarray(np.arange(1, 1 + 2 * b, dtype=np.int32).reshape(b, 2))
    pool = model.init_paged_cache(2 * b + 1, 4, b, 8)
    _, pool = model.prefill_chunk_paged(params, pool, toks,
                                        jnp.zeros((b,), jnp.int32), bt,
                                        active, valid)
    live = active[:, None] & (jnp.arange(width)[None] < valid[:, None])
    want = _hand_count(params, toks, live, TINY)
    assert tuple(int(c) for c in pool["moe_counts"]) == want
    assert want[0] == 10 * TINY.num_experts_per_token * (TINY.num_layers - 1)


def test_the_engine_reads_the_routing_counters_with_its_tokens():
    """The engine's counters follow the device's sums: every live token,
    prompt or decode, routed once in each expert layer."""
    model = build_model(TINY)
    params = _params(model, jax.random.key(12))
    eng = InferenceEngine(model, max_slots=2, max_seq=32, page_size=8,
                          prefill_chunk=4, policy="chunked")
    eng.load_params(params)
    rng = np.random.default_rng(13)
    for i, n in enumerate((9, 6, 4)):
        eng.submit(Request(i, rng.integers(0, TINY.vocab_size, n)
                           .astype(np.int32), 3))
    eng.run()
    st = eng.stats
    tokens = st.prefill_tokens + st.decode_tokens
    per = TINY.num_experts_per_token * (TINY.num_layers - 1)
    assert st.expert_picks == per * tokens
    assert 0 < st.expert_picks_held < st.expert_picks
    assert st.expert_picks_peak * TINY.experts_held >= st.expert_picks_held
