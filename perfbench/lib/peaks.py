"""Published peaks by ``device_kind``, and the operations a training token
needs. A device that is not in the table is an error, never a default."""

from __future__ import annotations

#: one chip. Source: Google Cloud documentation, "TPU v5e" (system
#: architecture): 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s. JAX reports
#: that chip's ``device_kind`` as "TPU v5 lite". (Copied from
#: ``bench.PEAK_TFLOPS_BY_KIND``; PERF.md lists the original for deletion.)
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            "perfbench/lib/peaks.py with its source"
        )
    return PEAKS[device_kind]


def matmul_params(cfg: dict) -> int:
    """Parameters that a token passes through a matrix multiplication
    with: the four attention projections and the two MLP matrices of every
    layer, and the tied output head. Embedding lookup, positions, norms and
    biases multiply nothing."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes require for one token of
    a ``seq_len``-token causal sequence: 6 per matmul parameter (2 forward,
    4 backward), plus attention. A token attends to ``(seq_len + 1) / 2``
    positions on average; QK^T and PV are ``2 * n_embd`` each per attended
    position forward, and twice that backward: ``12 * n_embd`` per attended
    position per layer. Recomputation (the flash backward's, the CE head's)
    is not counted."""
    attended = (seq_len + 1) / 2.0
    attn = cfg["n_layer"] * 12.0 * cfg["n_embd"] * attended
    return 6.0 * matmul_params(cfg) + attn
