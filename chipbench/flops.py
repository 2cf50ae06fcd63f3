"""Operations and bytes of the model programs, computed from their shapes.

Operations are the multiply-adds of every matrix product, two per
multiply-add; element-wise work (GELU, layer norm, softmax, pooling) is not
counted.  Bytes are the least a dispatch must move through HBM: its inputs,
its outputs and its weights once, in float32.  Only rows the program had
to compute count: the padding rows of a bucket and the replica graphs that
fill the last GTN chunk do not.
"""
from __future__ import annotations

F32 = 4


def gtn_graph_flops(g: dict, nodes: int) -> int:
    """One graph of ``nodes`` (padded) operator nodes through the GTN."""
    n, d, h = nodes, g["d_model"], g["n_heads"]
    dh = d // h
    f = 2 * n * g["feat_dim"] * d + 2 * n * g["pe_dim"] * d
    per_layer = (2 * n * d * 3 * d            # qkv
                 + 2 * h * n * n * dh         # q k^T
                 + 2 * h * n * n * 3          # structure-flag bias
                 + 2 * h * n * n * dh         # attention x v
                 + 2 * n * d * d              # output projection
                 + 2 * 2 * n * d * g["d_ff"])  # feed-forward
    return f + g["n_layers"] * per_layer


def gtn_weight_bytes(g: dict) -> int:
    d, ff = g["d_model"], g["d_ff"]
    w = (g["feat_dim"] + 1) * d + (g["pe_dim"] + 1) * d
    layer = (d + 1) * 3 * d + (d + 1) * d + g["n_heads"] * 3 + 4 * d \
        + (d + 1) * ff + (ff + 1) * d
    return F32 * (w + g["n_layers"] * layer)


def gtn_graph_bytes(g: dict, nodes: int) -> int:
    """Inputs (features, encodings, structure flags, mask) and output."""
    n = nodes
    return F32 * (n * g["feat_dim"] + n * g["pe_dim"] + n * n * 3
                  + g["d_model"]) + n


def head_dims(g: dict, hidden, theta_dim: int, n_targets: int,
              nond_dim: int = 12):
    return [g["d_model"] + theta_dim + nond_dim, *hidden, n_targets]


def head_row_flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def head_weight_bytes(dims) -> int:
    return F32 * sum((a + 1) * b for a, b in zip(dims[:-1], dims[1:]))


def head_row_bytes(dims) -> int:
    return F32 * (dims[0] + dims[-1])


def roofline_s(flops: float, bytes_: float, peak: dict):
    """(least seconds, which bound) on the chip of ``peak``."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
