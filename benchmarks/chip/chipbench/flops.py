"""Operations and bytes the algorithm needs, from shapes, and the table of
peaks they are measured against.

Every count is of useful work: live rows only, each at its true length. A
kernel that computes masked rows, or streams pages past a row's length,
reads below its roofline here; no count can exceed what the work needs, so
no share computed from these can pass 100% unless the time is short.
"""
from __future__ import annotations

import dataclasses

#: Peaks per chip, keyed by ``jax.Device.device_kind``. Source: Google Cloud
#: documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


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


# ---------------------------------------------------------------- kernels

def decode_attention(m: Dense, lengths) -> tuple[float, float]:
    """(FLOPs, bytes) of one paged decode attention call (one layer) over
    live rows that attend ``lengths`` keys each (the new token included):
    QK and PV at 2 FLOPs a multiply-add, K and V read once, q read and the
    output written."""
    keys = sum(int(n) for n in lengths)
    rows = len(lengths)
    flops = 4.0 * m.heads * m.head_dim * keys
    byts = m.bytes_per_el * (2 * keys * m.kv_heads * m.head_dim
                             + 2 * rows * m.heads * m.head_dim)
    return flops, float(byts)


def prefill_attention(m: Dense, rows) -> tuple[float, float]:
    """(FLOPs, bytes) of one paged prefill attention call (one layer).
    ``rows``: (start, c) of each live row: c new queries at positions
    ``start .. start+c-1``, each attending causally to every key up to its
    own position. K and V of ``start + c`` positions are read once."""
    flops = byts = 0.0
    for start, c in rows:
        start, c = int(start), int(c)
        keys = c * start + c * (c + 1) // 2
        flops += 4.0 * m.heads * m.head_dim * keys
        byts += m.bytes_per_el * (2 * (start + c) * m.kv_heads * m.head_dim
                                  + 2 * c * m.heads * m.head_dim)
    return flops, byts


# ------------------------------------------------------------ whole steps

def step_flops(m: Dense, *, prefill_rows=(), decode_lengths=()) -> float:
    """Useful model FLOPs of one engine dispatch, all layers: the matmuls
    of every live token, causal attention at its true length, and the
    unembedding of decode tokens only (prefill logits are never used)."""
    tokens = sum(int(c) for _, c in prefill_rows) + len(decode_lengths)
    flops = 2.0 * m.layer_matmul_params * m.layers * tokens
    flops += m.layers * prefill_attention(m, prefill_rows)[0]
    flops += m.layers * decode_attention(m, decode_lengths)[0]
    flops += 2.0 * m.d_model * m.vocab * len(decode_lengths)
    return flops
