"""Operations and bytes the algorithm needs, counted from a configuration's
sizes.  They count the work, whatever computes it: a later change that
fuses or drops a kernel cannot push a share past what the chip can do.

Matrix products count 2 FLOPs per multiply-add; elementwise work (norms,
activations, softmax) is left out.  Attention counts the keys a query
really attends (its causal context), not a cache's padded length.
"""
from __future__ import annotations


def matmul_params(s) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = s["d"], s["head_dim"]
    hq, hkv = s["heads"] * hd, s["kv_heads"] * hd
    return 2 * d * hq + 2 * d * hkv + 3 * d * s["ff"]


def attention_flops(s, pairs: int) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs, one layer."""
    return 4.0 * s["heads"] * s["head_dim"] * pairs


def causal_pairs(P: int) -> int:
    return P * (P + 1) // 2


def head_flops(s) -> float:
    return 2.0 * s["d"] * s["vocab"]


def prefill_flops(s, P: int) -> float:
    """A batch-1 prefill of ``P`` tokens: the layers, and the LM head for
    the last position (the only logits a prefill returns)."""
    return s["layers"] * (2.0 * P * matmul_params(s)
                          + attention_flops(s, causal_pairs(P))) \
        + head_flops(s)


def decode_flops(s, context: int) -> float:
    """One decoded token that attends ``context`` keys (itself included)."""
    return s["layers"] * (2.0 * matmul_params(s)
                          + attention_flops(s, context)) + head_flops(s)


def swiglu_call(s, rows: int):
    """(FLOPs, bytes) of one gated MLP of ``rows`` tokens: three products,
    bf16 weights read once, activations in and out."""
    d, f = s["d"], s["ff"]
    return 6.0 * rows * d * f, 2.0 * (3 * d * f + 2 * rows * d)


def flash_call(s, P: int):
    """(FLOPs, bytes) of one causal prefill attention over ``P`` tokens:
    q, k, v read and o written once, in bf16."""
    hq, hkv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return (attention_flops(s, causal_pairs(P)),
            2.0 * P * (2 * hq + 2 * hkv))


def least_time(flops: float, nbytes: float, peaks):
    """The roofline's least time and which bound sets it."""
    tc, tm = flops / peaks.flops_bf16, nbytes / peaks.hbm_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
