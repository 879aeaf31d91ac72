"""The yardstick's arithmetic: operations and bytes of the work a cell
asks for, counted from its true shapes, never from how a kernel pads
them. Any implementation of the same work is held to the same count.
"""
from __future__ import annotations

F32 = 4


def ota_slot(trajectories: int, n_nodes: int, dim: int,
             itemsize: int = F32) -> dict:
    """One over-the-air slot for `trajectories` trajectories at once:
    v = (1/N) sum_n h_n g_n + w. Reads the (T, N, d) gradients, the (T, N)
    gains and the (T, d) noise, writes the (T, d) update; one multiply and
    one add per gradient element."""
    t, n, d = trajectories, n_nodes, dim
    return {"flops": 2 * t * n * d,
            "bytes": itemsize * (t * n * d + t * n + 2 * t * d)}


def roofline_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak HBM bandwidth."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def decoder_params(cfg: dict) -> int:
    """Parameters of a dense pre-norm decoder (RMS norms with scales,
    gated MLP when `glu`, embedding tied to the output when
    `tie_embeddings`)."""
    d, v, layers = cfg["d_model"], cfg["vocab_size"], cfg["n_layers"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if cfg["glu"] else 2) * d * cfg["d_ff"]
    per_layer = attn + mlp + 2 * d
    embed = v * d * (1 if cfg["tie_embeddings"] else 2)
    return layers * per_layer + embed + d


def decoder_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per token: 6 per matmul parameter
    (the input embedding's lookup is no matmul, the tied output
    projection is) plus 12 * layers * heads * head_dim * seq_len for the
    attention scores and values. Recomputation is not counted."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    matmul_params = decoder_params(cfg) - v * d * (
        0 if cfg["tie_embeddings"] else 1)
    matmul_params -= cfg["n_layers"] * 2 * d + d  # norm scales
    attn = 12 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * seq_len
    return 6.0 * matmul_params + attn
