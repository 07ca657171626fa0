"""The dense decoder block: what the benchmark needs to judge and count a
configuration that states ``"block": "dense"``.

The block (see each configuration's ``assumed``): RMSNorm before attention
and before the MLP, rotary embedding over the whole head in split halves,
grouped-query attention, SwiGLU, untied head, no biases. Its float32
reference is one layer function run by ``chipbench.reference``'s driver;
its work is counted by ``chipbench.flops``'s GQA attention counts; its step
programs are told apart in a trace by the paged attention kernel each one
holds.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import flops, reference
from chipbench.reference import attend, mm, rmsnorm, rope

#: the parameter tree the reference computes; anything else is refused
LEAVES = {"embedding", "lm_head", "ln_f", "layers/ln1", "layers/ln2",
          "layers/attn/wq", "layers/attn/wk", "layers/attn/wv",
          "layers/attn/wo", "layers/ffn/w_gate", "layers/ffn/w_up",
          "layers/ffn/w_down"}
#: groups of the tree whose leaves are stacked over layers
STACKED = ("layers/",)
#: kernel name (the prefix of its operation's name) -> the step it marks
KERNELS = {"paged_prefill_attention": "prefill",
           "paged_decode_attention": "decode"}


def check_tree(specs: dict) -> None:
    if set(specs) != LEAVES:
        raise ValueError(
            "the program's parameters are not the dense decoder the "
            f"reference computes: extra {sorted(set(specs) - LEAVES)},"
            f" missing {sorted(LEAVES - set(specs))}")


# -------------------------------------------------------------- reference

@functools.partial(jax.jit, static_argnames=("blk", "control"))
def _layer(x, key, layer, *, blk, control):
    def w(name):
        return blk.draw(key, name, layer)

    b, s, dm = x.shape
    pos = jnp.arange(s)
    wq, wk, wv, wo = (w("layers/attn/wq"), w("layers/attn/wk"),
                      w("layers/attn/wv"), w("layers/attn/wo"))
    h, hd = wq.shape[1], wq.shape[2]
    kvh = wk.shape[1]
    a = rmsnorm(x, w("layers/ln1"), blk.eps)
    q = mm(a, wq.reshape(dm, h * hd), control).reshape(b, s, h, hd)
    k = mm(a, wk.reshape(dm, kvh * hd), control).reshape(b, s, kvh, hd)
    v = mm(a, wv.reshape(dm, kvh * hd), control).reshape(b, s, kvh, hd)
    o = attend(rope(q, pos, blk.theta), rope(k, pos, blk.theta), v)
    x = x + mm(o.reshape(b, s, h * hd), wo.reshape(h * hd, dm), control)
    m = rmsnorm(x, w("layers/ln2"), blk.eps)
    gate = mm(m, w("layers/ffn/w_gate"), control)
    up = mm(m, w("layers/ffn/w_up"), control)
    return x + mm(jax.nn.silu(gate) * up, w("layers/ffn/w_down"), control)


def logits_at(cfg: dict, specs: dict, seed: int, sequences: list,
              control: bool = False) -> list:
    """Float32 logits ``(K, vocab)`` of each sequence at its served
    positions (see ``reference.logits_at``); with ``control``, every
    projection in float8."""
    check_tree(specs)
    blk = reference.Block.of(cfg, specs, layers=specs["layers/ln1"][0][0])
    return reference.logits_at(blk, _layer, seed, sequences, control)


# ------------------------------------------------------------------- work

@dataclasses.dataclass(frozen=True)
class Dense:
    """The widths of a dense decoder (SwiGLU MLP, untied head)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    bytes_per_el: int = 2     # bf16 weights, activations and KV

    @classmethod
    def of(cls, cfg: dict) -> "Dense":
        return cls(layers=cfg["num_layers"], d_model=cfg["d_model"],
                   heads=cfg["num_heads"], kv_heads=cfg["num_kv_heads"],
                   head_dim=cfg["head_dim"] or cfg["d_model"] // cfg["num_heads"],
                   d_ff=cfg["d_ff"], vocab=cfg["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o projections and the three MLP matrices of a layer."""
        d, hd = self.d_model, self.head_dim
        return (d * self.heads * hd + 2 * d * self.kv_heads * hd
                + self.heads * hd * d + 3 * d * self.d_ff)


def step_flops(m: Dense, *, prefill_rows=(), decode_lengths=()) -> float:
    """Useful model FLOPs of one engine dispatch, all layers: the matmuls
    of every live token, causal attention at its true length, and the
    unembedding of decode tokens only (prefill logits are never used)."""
    tokens = sum(int(c) for _, c in prefill_rows) + len(decode_lengths)
    fl = 2.0 * m.layer_matmul_params * m.layers * tokens
    fl += m.layers * flops.prefill_attention(m, prefill_rows)[0]
    fl += m.layers * flops.decode_attention(m, decode_lengths)[0]
    fl += 2.0 * m.d_model * m.vocab * len(decode_lengths)
    return fl


def work(cfg: dict, prefill_rows, decode_lengths) -> tuple:
    """Useful work of the window's dispatches: whole-step FLOPs, and each
    kernel's ``(FLOPs, bytes)`` over all layers. ``prefill_rows``:
    ``(start, c)`` of every live prefill row; ``decode_lengths``: the keys
    each live decode row attends, the new token included."""
    m = Dense.of(cfg)
    pf, pb = flops.prefill_attention(m, prefill_rows)
    df, db = flops.decode_attention(m, decode_lengths)
    return (step_flops(m, prefill_rows=prefill_rows,
                       decode_lengths=decode_lengths),
            {"paged_prefill_attention": (m.layers * pf, m.layers * pb),
             "paged_decode_attention": (m.layers * df, m.layers * db)})
