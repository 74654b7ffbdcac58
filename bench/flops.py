"""Operations and bytes of one serving step, from the configuration's
shapes and the step's live rows (never its padded rows).

A step is a list of rows, each ``(n, p)``: ``n`` new tokens written at
positions ``p .. p+n-1`` (decode: ``n = 1``). Per live token the model does
the weight GEMMs of every layer and the ``lm_head``; attention does
``4 * n_heads * head_dim`` operations per (query, key) pair it reads, and a
row's pairs are ``sum_j (p + j + 1)`` under the causal mask.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Row = Tuple[int, int]


def gemm_sites(m: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, count per step) of every weight GEMM."""
    d, q, kv = m["d_model"], m["n_heads"] * m["head_dim"], \
        m["kv_heads"] * m["head_dim"]
    per_layer = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
                 ("wi", d, m["d_ff"]), ("wo_ffn", m["d_ff"], d)]
    return ([(n, k, nn, m["n_layers"]) for n, k, nn in per_layer]
            + [("lm_head", d, m["vocab"], 1)])


def param_count(m: Dict) -> int:
    """Parameters of the program's tree: embedding, per-layer weights,
    biases and LayerNorms, final LayerNorm, untied lm_head."""
    d = m["d_model"]
    q, kv = m["n_heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    layer = (d * q + q) + 2 * (d * kv + kv) + (q * d + d) \
        + (d * m["d_ff"] + m["d_ff"]) + (m["d_ff"] * d + d) + 4 * d
    return m["vocab"] * d * 2 + 2 * d + m["n_layers"] * layer


def attention_pairs(rows: Iterable[Row]) -> int:
    return sum(n * p + n * (n + 1) // 2 for n, p in rows)


def step_flops(m: Dict, rows: Iterable[Row]) -> float:
    rows = list(rows)
    tokens = sum(n for n, _ in rows)
    gemm = sum(2.0 * tokens * k * n * c for _, k, n, c in gemm_sites(m))
    attn = 4.0 * m["n_heads"] * m["head_dim"] * attention_pairs(rows) \
        * m["n_layers"]
    return gemm + attn


def gemm_roofline_s(m: Dict, rows: Iterable[Row], pk: Dict) -> float:
    """Least time the chip could take for the step's weight GEMMs: per
    GEMM the larger of its operations over peak and its bytes (bf16
    weights and inputs, float32 outputs) over HBM bandwidth."""
    tokens = sum(n for n, _ in rows)
    total = 0.0
    for _, k, n, c in gemm_sites(m):
        ops = 2.0 * tokens * k * n
        moved = 2.0 * (k * n + tokens * k) + 4.0 * tokens * n
        total += c * max(ops / pk["bf16_flops"], moved / pk["hbm_bytes_per_s"])
    return total
