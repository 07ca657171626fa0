"""Operations and bytes the algorithm needs, from shapes, and the table of
peaks they are measured against. A block (``blocks/<name>.py``) counts its
own work with these kernel counts, or with counts of its own.

Every count is of useful work: live rows only, each at its true length. A
kernel that computes masked rows, or streams pages past a row's length,
reads below its roofline here; no count can exceed what the work needs, so
no share computed from these can pass 100% unless the time is short.
"""
from __future__ import annotations

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


# ------------------------------------------------------- GQA attention
# ``m``: the widths of a grouped-query attention layer: ``heads``,
# ``kv_heads``, ``head_dim`` and ``bytes_per_el`` (of K, V, q and output).

def decode_attention(m, lengths) -> tuple[float, float]:
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


def prefill_attention(m, rows) -> tuple[float, float]:
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
