"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's body in Python and never asks the TPU
compiler about tiling, so a kernel can pass every parity test and still be
refused on the chip. These tests lower each kernel with ``interpret=False``
for one chip of a described ``v5e:2x2`` topology and compile it, at the
registry's widths: stablelm-3b for the attention kernels and rmsnorm,
moonshot-v1-16b-a3b (Moonlight) for the latent-attention kernels,
mamba2-1.3b for the SSD scan. Nothing runs, so results and times are not
checked here; the TPU compiler only has to accept the kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers import
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import CONFIGS
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_mla_decode_attention import \
    paged_mla_decode_attention
from repro.kernels.paged_mla_prefill_attention import \
    paged_mla_prefill_attention
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.kernels.prefill_attention import prefill_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_chunk_scan

ATTN = CONFIGS["stablelm-3b"]
MLA = CONFIGS["moonshot-v1-16b-a3b"]
SSM = CONFIGS["mamba2-1.3b"]
B, SEQ, CHUNK = 4, 256, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _cases(page: int):
    """kernel name -> (function, argument shapes as (shape, dtype))."""
    h, kv, d = ATTN.num_heads, ATTN.num_kv_heads, ATTN.resolved_head_dim
    theta = ATTN.rope_theta
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    pool = ((B * SEQ // page, kv, d, page), bf)
    table = ((B, SEQ // page), i32)
    q_len = SSM.ssm_chunk
    mh, w, r = MLA.num_heads, MLA.latent_width, MLA.kv_lora_rank
    latent = ((MLA.num_layers, B * SEQ // page, 1, w, page), bf)
    scale = 1.0 / (MLA.qk_nope_head_dim + MLA.qk_rope_head_dim) ** 0.5
    return {
        "paged_mla_decode_attention": (
            lambda q, c, bt, ln: paged_mla_decode_attention(
                q, c, bt, ln, 3, scale=scale, rank=r),
            [((B, mh, w), f32), latent, table, ((B,), i32)]),
        "paged_mla_prefill_attention": (
            lambda q, c, bt, st: paged_mla_prefill_attention(
                q, c, bt, st, 3, heads=mh, scale=scale, rank=r),
            [((B, CHUNK * mh, w), f32), latent, table, ((B,), i32)]),
        "decode_attention": (
            lambda q, k, v, ln: decode_attention(q, k, v, ln,
                                                 rope_theta=theta),
            [((B, h, d), bf), ((B, kv, SEQ, d), bf), ((B, kv, SEQ, d), bf),
             ((B,), i32)]),
        "prefill_attention": (
            lambda q, k, v, st: prefill_attention(q, k, v, st,
                                                  rope_theta=theta),
            [((B, h, CHUNK, d), bf), ((B, kv, SEQ, d), bf),
             ((B, kv, SEQ, d), bf), ((B,), i32)]),
        "paged_decode_attention": (
            lambda q, k, v, bt, ln: paged_decode_attention(
                q, k, v, bt, ln, rope_theta=theta),
            [((B, h, d), bf), pool, pool, table, ((B,), i32)]),
        "paged_prefill_attention": (
            lambda q, k, v, bt, st: paged_prefill_attention(
                q, k, v, bt, st, rope_theta=theta),
            [((B, h, CHUNK, d), bf), pool, pool, table, ((B,), i32)]),
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            [((1, h, SEQ, d), bf), ((1, kv, SEQ, d), bf),
             ((1, kv, SEQ, d), bf)]),
        "rmsnorm": (
            lambda x, w: rmsnorm(x, w),
            [((B * CHUNK, ATTN.d_model), bf), ((ATTN.d_model,), f32)]),
        "ssd_chunk_scan": (
            lambda x, dt, cum, b_, c_: ssd_chunk_scan(x, dt, cum, b_, c_),
            [((B, q_len, SSM.ssm_num_heads, SSM.ssm_head_dim), bf),
             ((B, q_len, SSM.ssm_num_heads), f32),
             ((B, q_len, SSM.ssm_num_heads), f32),
             ((B, q_len, SSM.ssm_state), bf),
             ((B, q_len, SSM.ssm_state), bf)]),
    }


PAGED = ("paged_decode_attention", "paged_prefill_attention",
         "paged_mla_decode_attention", "paged_mla_prefill_attention")
# every kernel at a 16-token page; the paged kernels also at the smallest
# page the autotuner offers and at the one it picks for the serving
# launcher's 128-token window, both of which must compile for bf16 pools
CASES = ([(k, 16) for k in sorted(_cases(16))]
         + [(k, page) for k in PAGED for page in (8, 128)])


@pytest.mark.parametrize("kernel,page", CASES)
def test_kernel_compiles_for_v5e(kernel, page, one_chip, no_compile_cache):
    fn, shapes = _cases(page)[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode_paged", "prefill_paged"])
def test_paged_step_updates_the_pool_in_place_on_v5e(program, one_chip,
                                                     no_compile_cache):
    """The engine's paged step programs for stablelm-3b at full width,
    compiled for one v5e: the pool comes in donated and goes out aliased to
    it, and no pool-sized copy lies between (the layer scan carries the
    pool, the kernels read it by layer, and its default layout is the one
    the kernels read)."""
    _check_in_place(ATTN, program, "k_pages", one_chip)


@pytest.mark.parametrize("program", ["decode_paged", "prefill_paged"])
def test_latent_step_updates_the_pool_in_place_on_v5e(program, one_chip,
                                                      no_compile_cache):
    """As above for Moonlight's step programs at full width with its share
    of 16 experts: the latent pool and the routing counters come in donated
    and go out aliased, with no pool-sized copy between; the latent
    attention kernels are in them."""
    import dataclasses
    text = _check_in_place(dataclasses.replace(MLA, experts_held=16),
                           program, "latent_pages", one_chip)
    kernel = ("paged_mla_decode_attention" if program == "decode_paged"
              else "paged_mla_prefill_attention")
    assert kernel in text


def _check_in_place(cfg, program, pool_key, one_chip) -> str:
    import re

    from repro.kernels import ops
    from repro.models.factory import build_model
    from repro.serving.engine import InferenceEngine
    model = build_model(cfg)
    pages, page, blocks = 8, 256, 4
    jitted = getattr(InferenceEngine(model, max_slots=1, max_seq=page,
                                     kv_pages=1, page_size=page),
                     f"_jit_{program}")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    shapes = jax.eval_shape(
        lambda: model.init_paged_cache(pages, page, B, page * blocks))
    params = model.abstract_params(jnp.bfloat16)
    i32 = jnp.int32
    rows = ((B, 1) if program == "decode_paged" else (B, page), i32)
    args = [params, shapes, jax.ShapeDtypeStruct(*rows),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B, blocks), i32),
            jax.ShapeDtypeStruct((B,), jnp.bool_)]
    if program == "prefill_paged":
        args.append(jax.ShapeDtypeStruct((B,), i32))
    try:
        ops.set_backend("pallas")
        text = jitted.lower(*on_chip(args)).compile().as_text()
    finally:
        ops.set_backend(None)
    assert "tpu_custom_call" in text
    pool = "bf16[%d,%d,%d,%d,%d]" % shapes[pool_key].shape
    copies = [line[:120] for line in text.splitlines()
              if f"= {pool}" in line and re.search(r" copy(-start)?\(", line)]
    assert not copies
    n = len(jax.tree.leaves(params))         # the cache follows the params
    aliased = {int(i) for i in re.findall(r"\((\d+), \{\}, \w+-alias\)",
                                          text.splitlines()[0])}
    assert set(range(n, n + len(shapes))) <= aliased
    return text
