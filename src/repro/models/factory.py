"""Uniform model API over all families: build once, use everywhere
(training loop, serving engine, dry-run, benchmarks).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, hybrid, layers, mamba_lm, transformer

Array = jax.Array
Params = Any
Cache = Any


@dataclass
class ModelBundle:
    cfg: ModelConfig

    # ------------------------------------------------------------- params
    def init(self, key, dtype=jnp.float32) -> Params:
        f = self.cfg.family
        if f in ("dense", "moe", "vlm"):
            return transformer.init_params(key, self.cfg, dtype)
        if f == "ssm":
            return mamba_lm.init_params(key, self.cfg, dtype)
        if f == "hybrid":
            return hybrid.init_params(key, self.cfg, dtype)
        if f == "encdec":
            return encdec.init_params(key, self.cfg, dtype)
        raise ValueError(f"unknown family {f}")

    def abstract_params(self, dtype=jnp.bfloat16):
        """ShapeDtypeStructs for every parameter — no allocation."""
        return jax.eval_shape(lambda: self.init(jax.random.key(0), dtype))

    # ------------------------------------------------------------ forward
    def forward(self, params: Params, batch: dict, *, remat: str = "full"):
        """batch -> (logits, aux). Train/eval full-sequence pass."""
        f = self.cfg.family
        if f == "encdec":
            return encdec.forward(params, batch["frames"], batch["tokens"],
                                  self.cfg, remat=remat)
        if f == "ssm":
            return mamba_lm.forward(params, batch["tokens"], self.cfg, remat=remat)
        if f == "hybrid":
            return hybrid.forward(params, batch["tokens"], self.cfg, remat=remat)
        return transformer.forward(params, batch["tokens"], self.cfg,
                                   remat=remat, embeds=batch.get("embeds"))

    def loss_fn(self, params: Params, batch: dict, *, remat: str = "full"):
        """Next-token xent (+0.01·aux for MoE balance)."""
        logits, aux = self.forward(params, batch, remat=remat)
        tokens = batch["tokens"]
        loss = layers.cross_entropy(logits[:, :-1], tokens[:, 1:],
                                    batch.get("mask"))
        return loss + 0.01 * aux

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Cache:
        f = self.cfg.family
        if f == "ssm":
            return mamba_lm.init_cache(self.cfg, batch, max_seq, dtype)
        if f == "hybrid":
            return hybrid.init_cache(self.cfg, batch, max_seq, dtype)
        if f == "encdec":
            return encdec.init_cache(self.cfg, batch, max_seq, dtype)
        return transformer.init_cache(self.cfg, batch, max_seq, dtype)

    # ------------------------------------------------------ paged serving
    #: cache leaves that live in the shared page pool (no batch axis);
    #: slot-slicing helpers pass them through untouched
    PAGE_KEYS = ("k_pages", "v_pages", "latent_pages")
    #: every cache leaf with no slot axis: the pools, and an expert model's
    #: routing counters (``moe_counts``), which no page copy touches
    SHARED_KEYS = PAGE_KEYS + ("moe_counts",)

    def cache_pages(self) -> bool:
        """Does this family support the paged KV cache? True for every
        family with growing attention KV (dense/moe/vlm transformers,
        hybrid attention sublayers, enc-dec decoder self-KV). False for
        pure SSM: its O(1) recurrent state is slot-resident by nature —
        there is nothing to page. int8 KV (``kv_cache_dtype`` hint) stays
        on the contiguous path."""
        from repro.distributed import hints
        if hints.get("kv_cache_dtype") == "int8":
            return False
        return self.cfg.family != "ssm"

    def prefix_shareable(self) -> bool:
        """Can finished requests' prompt KV be reused across requests
        (radix prefix cache)? Requires the ENTIRE prefill state to live in
        the page pool, so mapping a donor's pages reproduces the donor's
        state bit-exactly: true for the transformer families (dense, VLM
        text stacks, and MoE, whose served expert layers drop no token, so
        a row's KV never depends on its batch-mates). Hybrid keeps
        slot-resident SSM state and enc-dec keeps slot-resident cross-KV —
        pages alone don't carry their prefill state."""
        return (self.cache_pages()
                and self.cfg.family in ("dense", "vlm", "moe"))

    def copy_page(self, cache: Cache, src, dst) -> Cache:
        """Device copy of one pool row across every paged layer — the CoW
        fork that backs :meth:`BlockAllocator.fork_table`. ``src``/``dst``
        are page ids (traced scalars: one executable serves every fork)."""
        def one(path, leaf):
            if self._leaf_key(path) in self.PAGE_KEYS:
                return leaf.at[:, dst].set(leaf[:, src])
            return leaf
        return jax.tree_util.tree_map_with_path(one, cache)

    def init_paged_cache(self, num_pages: int, page_size: int, batch: int,
                         max_seq: int, dtype=jnp.bfloat16) -> Cache:
        """Page-pool cache: ``num_pages`` pages of ``page_size`` tokens per
        layer shared by all rows (block tables are engine-side); leaves
        that cannot page (hybrid SSM state, enc-dec cross-KV) remain
        slot-resident with a ``batch`` axis."""
        f = self.cfg.family
        if f == "hybrid":
            return hybrid.init_paged_cache(self.cfg, num_pages, page_size,
                                           batch, dtype)
        if f == "encdec":
            return encdec.init_paged_cache(self.cfg, num_pages, page_size,
                                           batch, max_seq, dtype)
        if f == "ssm":
            raise ValueError("family 'ssm' has no KV to page; "
                             "check cache_pages() first")
        return transformer.init_paged_cache(self.cfg, num_pages, page_size,
                                            dtype)

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """Device bytes ONE cached token costs across all paged layers —
        what sizes the page pool against a memory budget."""
        from repro.roofline.hw import kv_bytes_per_token
        return kv_bytes_per_token(self.cfg, dtype_bytes)

    def decode_step_paged(self, params: Params, cache: Cache, tokens: Array,
                          lengths: Array, block_tables: Array,
                          active: Array | None = None):
        """Paged :meth:`decode_step`: K/V resolved through ``block_tables``
        (B, nb) into the shared page pool. Token-identical to the
        contiguous path — parity pinned per family in tests/test_paged.py."""
        f = self.cfg.family
        if f == "hybrid":
            logits, new = hybrid.decode_step_paged(
                params, cache, tokens, lengths, block_tables, self.cfg,
                active)
        elif f == "encdec":
            logits, new = encdec.decode_step_paged(
                params, cache, tokens, lengths, block_tables, self.cfg,
                active)
        elif f == "ssm":
            raise ValueError("family 'ssm' has no paged decode path")
        else:
            logits, new = transformer.decode_step_paged(
                params, cache, tokens, lengths, block_tables, self.cfg,
                active)
        new = jax.tree.map(lambda n, o: n.astype(o.dtype), new, cache)
        return logits, new

    def prefill_chunk_paged(self, params: Params, cache: Cache,
                            tokens: Array, start_len: Array,
                            block_tables: Array,
                            active: Array | None = None,
                            valid: Array | None = None):
        """Paged :meth:`prefill_chunk`: chunk K/V scattered into the rows'
        pages; same one-dispatch-per-chunk hot path and ``valid``
        multi-slot contract."""
        f = self.cfg.family
        if f == "hybrid":
            logits, new = hybrid.prefill_chunk_paged(
                params, cache, tokens, start_len, block_tables, self.cfg,
                active, valid)
        elif f == "encdec":
            logits, new = encdec.prefill_chunk_paged(
                params, cache, tokens, start_len, block_tables, self.cfg,
                active, valid)
        elif f == "ssm":
            raise ValueError("family 'ssm' has no paged prefill path")
        else:
            logits, new = transformer.prefill_chunk_paged(
                params, cache, tokens, start_len, block_tables, self.cfg,
                active, valid)
        new = jax.tree.map(lambda n, o: n.astype(o.dtype), new, cache)
        return logits, new

    def prefill(self, params: Params, batch: dict, max_seq: int):
        f = self.cfg.family
        if f == "encdec":
            return encdec.prefill(params, batch["frames"], batch["tokens"],
                                  self.cfg, max_seq)
        if f == "ssm":
            return mamba_lm.prefill(params, batch["tokens"], self.cfg, max_seq)
        if f == "hybrid":
            return hybrid.prefill(params, batch["tokens"], self.cfg, max_seq)
        return transformer.prefill(params, batch["tokens"], self.cfg, max_seq,
                                   embeds=batch.get("embeds"))

    def decode_step(self, params: Params, cache: Cache, tokens: Array,
                    lengths: Array, active: Array | None = None):
        """One decode step for all B rows.

        ``active``: optional (B,) bool slot mask — rows where it is False
        keep their cache/state bit-identical (mask-isolated decode: the
        serving engine passes its slot mask instead of saving and restoring
        per-slot cache slices around every step). The returned cache is cast
        back to the input cache's dtypes so serving caches never drift
        upward to f32 across steps.
        """
        f = self.cfg.family
        if f == "ssm":
            logits, new = mamba_lm.decode_step(params, cache, tokens,
                                               lengths, self.cfg, active)
        elif f == "hybrid":
            logits, new = hybrid.decode_step(params, cache, tokens, lengths,
                                             self.cfg, active)
        elif f == "encdec":
            logits, new = encdec.decode_step(params, cache, tokens, lengths,
                                             self.cfg, active)
        else:
            logits, new = transformer.decode_step(params, cache, tokens,
                                                  lengths, self.cfg, active)
        new = jax.tree.map(lambda n, o: n.astype(o.dtype), new, cache)
        return logits, new

    def prefill_chunk(self, params: Params, cache: Cache, tokens: Array,
                      start_len: Array, active: Array | None = None,
                      valid: Array | None = None):
        """Advance every row's prefill by C tokens in ONE jitted dispatch.

        tokens: (B,C) int32; start_len: (B,) int32 tokens already cached per
        row; ``active``: optional (B,) bool — inactive rows are untouched.
        Returns (logits (B,C,V), new_cache). Parity with the token-stepped
        decode path is pinned per family in tests/test_serving.py.

        ``valid``: optional (B,) int32 per-row count of REAL chunk tokens
        (multi-slot batched prefill: one dispatch advances several
        mid-prefill slots by different amounts, pads at the tail). Pad
        tokens never touch the cache/state; their logits are garbage the
        engine discards. Every family's rows are independent here (served
        expert layers drop no token), so batched rows compute what
        per-row dispatches would.
        """
        f = self.cfg.family
        if f == "ssm":
            logits, new = mamba_lm.prefill_chunk(params, cache, tokens,
                                                 start_len, self.cfg, active,
                                                 valid)
        elif f == "hybrid":
            logits, new = hybrid.prefill_chunk(params, cache, tokens,
                                               start_len, self.cfg, active,
                                               valid)
        elif f == "encdec":
            logits, new = encdec.prefill_chunk(params, cache, tokens,
                                               start_len, self.cfg, active,
                                               valid)
        else:
            logits, new = transformer.prefill_chunk(params, cache, tokens,
                                                    start_len, self.cfg,
                                                    active, valid)
        new = jax.tree.map(lambda n, o: n.astype(o.dtype), new, cache)
        return logits, new

    # ---------------------------------------------------------- dry-run IO
    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        b, s = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if shape.kind in ("train", "prefill"):
            specs = {"tokens": tok}
            if self.cfg.family == "encdec":
                tf = encdec.frames_len(s)
                specs["frames"] = jax.ShapeDtypeStruct((b, tf, self.cfg.d_model),
                                                       jnp.bfloat16)
            return specs
        # decode kinds: one new token + per-row valid lengths
        return {
            "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            "lengths": jax.ShapeDtypeStruct((b,), jnp.int32),
        }

    def abstract_cache(self, shape: ShapeConfig, dtype=jnp.bfloat16):
        return jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, shape.seq_len, dtype))

    # ------------------------------------------------- cache slot slicing
    # (serving engine: per-slot isolation for prefill / state restore)
    def _leaf_key(self, path_entries) -> str:
        return str(getattr(path_entries[0], "key", path_entries[0]))

    def _cache_batch_axis(self, path_entries) -> int:
        top = self._leaf_key(path_entries)
        if self.cfg.family == "hybrid" and top == "ssm":
            return 2  # (nb, n_ssm, B, ...)
        return 1      # (L, B, ...)

    def slice_cache(self, cache: Cache, slot: int) -> Cache:
        """Per-slot view of the cache. Page-pool leaves have no batch axis
        (pages are shared, block tables are engine-side) and pass through
        whole, so a slice of a paged cache still zips against it in
        :meth:`set_cache_slice`."""
        def one(path, leaf):
            if self._leaf_key(path) in self.SHARED_KEYS:
                return leaf
            ax = self._cache_batch_axis(path)
            return jax.lax.slice_in_dim(leaf, slot, slot + 1, axis=ax)
        return jax.tree_util.tree_map_with_path(one, cache)

    def set_cache_slice(self, cache: Cache, slot: int, piece: Cache) -> Cache:
        """Write a per-slot piece back; page-pool leaves are left untouched
        (slot admission remaps block tables instead of copying pages)."""
        def one(path, leaf, pleaf):
            if self._leaf_key(path) in self.SHARED_KEYS:
                return leaf
            ax = self._cache_batch_axis(path)
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, pleaf.astype(leaf.dtype), slot, axis=ax)
        return jax.tree_util.tree_map_with_path(one, cache, piece)


def build_model(cfg: ModelConfig) -> ModelBundle:
    return ModelBundle(cfg)
