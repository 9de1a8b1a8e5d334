"""Operations and bytes of the work the benchmark times, from shapes alone.

The counts are the algorithm's, at logical (unpadded) shapes, whatever
implements it: padding, extra passes and recomputation are the
implementation's cost and show as a lower share of the roofline.
"""

from __future__ import annotations

F32 = 4


def pogo_vadam_matrix_flops(p: int, n: int) -> int:
    """One POGO step with a VAdam base on one (p, n) matrix: the six
    (p, p, n) products A = X X^T, B = X Geff^T, A Geff, B X, C = M M^T and
    C M, and the two (p, p, p) products C^2 and C^3 of the feasibility
    telemetry (X' X'^T from C). Elementwise work (moments, scalings,
    residual) is left out: it is O(p n), under 1% of the products here."""
    return 6 * 2 * p * p * n + 2 * 2 * p * p * p


def pogo_vadam_matrix_bytes(p: int, n: int) -> int:
    """HBM traffic of one fused step on one (p, n) fp32 matrix: read X, the
    gradient and the first moment, write X' and the first moment; the
    per-matrix second moment (read and write) and distance are 12 bytes."""
    return 5 * F32 * p * n + 3 * F32


def pogo_vadam_step(groups) -> dict:
    """Totals over ``groups``, an iterable of ``(count, p, n)``."""
    flops = sum(b * pogo_vadam_matrix_flops(p, n) for b, p, n in groups)
    nbytes = sum(b * pogo_vadam_matrix_bytes(p, n) for b, p, n in groups)
    return {"flops": flops, "bytes": nbytes}


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def model_flops_per_token(model: dict, seq_len: int) -> float:
    """Training operations per token: three times the forward pass (the
    backward pass costs two), with no recomputation counted. The forward
    pass is 2 operations per weight a token meets in a matrix product
    (attention projections, the MLP or the routed experts and router, the
    output head over the published vocabulary; the embedding lookup is no
    product) plus causal attention, QK^T and PV over the (S + 1) / 2
    positions a token sees on average."""
    d = model["d_model"]
    h = model["num_heads"]
    kv = model["num_kv_heads"]
    hd = model.get("head_dim") or d // h
    layers = model["num_layers"]
    attn_w = d * h * hd * 2 + d * kv * hd * 2
    if model.get("num_experts"):
        ffn_w = (model["num_experts_per_token"] * 3 * d * model["moe_d_ff"]
                 + d * model["num_experts"])
    else:
        ffn_w = 3 * d * model["d_ff"]
    head_w = model["vocab_size"] * d
    weights = layers * (attn_w + ffn_w) + head_w
    attn = layers * 2 * 2 * h * hd * (seq_len + 1) / 2
    return 3.0 * (2.0 * weights + attn)
