"""``flops.py`` against hand counts of one head and one GTN dispatch."""
import json
import os

from chipbench import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def _model():
    with open(os.path.join(HERE, "..", "configs", "tpch_sf100.json")) as f:
        return json.load(f)["model"]


def test_head_dispatch_by_hand():
    mc = _model()
    dims = flops.head_dims(mc["gtn"], mc["hidden"], mc["theta_dim"]["subq"],
                           mc["n_targets"])
    # 48 (embedding) + 19 (theta) + 12 (alpha, beta, gamma) -> 128 -> 96 -> 2
    assert dims == [79, 128, 96, 2]
    per_row = 2 * (79 * 128 + 128 * 96 + 96 * 2)
    assert per_row == 45184
    assert flops.head_row_flops(dims) == per_row
    # A dispatch of 100 rows: inputs and outputs of each row, one weight set.
    weights = 4 * (80 * 128 + 129 * 96 + 97 * 2)
    assert flops.head_weight_bytes(dims) == weights
    assert 100 * flops.head_row_bytes(dims) == 100 * 4 * (79 + 2)


def test_gtn_dispatch_by_hand():
    g = _model()["gtn"]
    n, d, h, ff = 4, 48, 4, 96
    proj = 2 * n * 20 * d + 2 * n * 4 * d                  # 9216
    layer = (2 * n * d * 3 * d                             # qkv: 55296
             + 2 * h * n * n * (d // h)                    # q k^T: 1536
             + 2 * h * n * n * 3                           # flag bias: 384
             + 2 * h * n * n * (d // h)                    # w v: 1536
             + 2 * n * d * d                               # out: 18432
             + 2 * 2 * n * d * ff)                         # ffn: 73728
    assert proj + 2 * layer == 311040
    assert flops.gtn_graph_flops(g, n) == 311040
    # One dispatch of 64 graphs.
    assert 64 * flops.gtn_graph_flops(g, n) == 19906560
    assert flops.gtn_graph_bytes(g, n) == 4 * (4 * 20 + 4 * 4 + 16 * 3 + 48) + 4


def test_roofline_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000.0, 50.0, peak) == (10.0, "compute")
    assert flops.roofline_s(100.0, 50.0, peak) == (5.0, "memory")
