"""Operations and bytes a kernel's call needs, computed from shapes. The yardstick's half of every
roofline share: the program supplies only the time. What a whole block needs for a trained token
is its family's to count (``benchmark/families/<family>.py::train_flops_per_token``).

Sizes come from a configuration file's published keys (``hidden_size`` ...), never from the
program's own config object.
"""

from __future__ import annotations


def attention_flops_fwd(c: dict, batch: int, seq: int, causal: bool = True) -> float:
    """Forward FLOPs of the attention scores and values for one layer: QK^T and PV, each
    2*T*T*hd per head, halved by the causal mask (the flops the algorithm needs, not the ones
    a kernel that computes masked blocks spends)."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    f = 4.0 * batch * c["num_attention_heads"] * seq * seq * hd
    return f / 2 if causal else f


def flash_roofline(c: dict, batch: int, seq: int, peaks: dict, itemsize: int = 2) -> dict:
    """The least time one call of the flash attention forward (``fwd``) and one of its backward
    (``bwd``) can take for one layer on a chip with ``peaks``: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s, with which of the two bounds it.

    FLOPs, causal: the forward is QK^T and PV (2 matmuls); the backward recomputes QK^T and
    computes dP, dV, dQ, dK (5 matmuls), however many kernels it is split into. Bytes: the
    forward reads q, k, v and writes o (k, v at the KV-head count); the backward reads q, k, v,
    o, do and writes dq, dk, dv. The log-sum-exp rows are left out (1/128 of o)."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    fwd_flops = attention_flops_fwd(c, batch, seq)
    q_bytes = batch * nh * seq * hd * itemsize
    kv_bytes = batch * nkv * seq * hd * itemsize
    out = {}
    for name, flops, nbytes in (("fwd", fwd_flops, 2 * q_bytes + 2 * kv_bytes),
                                ("bwd", 2.5 * fwd_flops, 4 * q_bytes + 4 * kv_bytes)):
        t_flops, t_bytes = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
        out[name] = {"flops": flops, "bytes": nbytes, "min_s": max(t_flops, t_bytes),
                     "bound": "compute" if t_flops >= t_bytes else "memory"}
    return out
