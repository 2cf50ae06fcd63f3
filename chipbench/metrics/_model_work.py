"""Operations and bytes the window's model dispatches had to compute.

Shared by the kernel and model readers.  Counts come from the benchmark's
probes (graphs newly embedded, regressor rows before padding) and the
dispatch counts from the trace's programs.
"""
from chipbench import flops

GTN_PROGRAM = "jit__embed_batch"
HEAD_PROGRAM = "jit__head_fn"


def gtn(run):
    mc = run["cfg"]["model"]
    g, nodes = mc["gtn"], mc["graph_nodes"]
    graphs = sum(v for k, v in run["probes"].counts.items()
                 if k.startswith("embed_many."))
    calls = run["trace"]["programs"].get(GTN_PROGRAM, {}).get("calls", 0)
    return (graphs * flops.gtn_graph_flops(g, nodes),
            graphs * flops.gtn_graph_bytes(g, nodes)
            + calls * flops.gtn_weight_bytes(g))


def head(run):
    mc = run["cfg"]["model"]
    f = b = 0
    calls = run["trace"]["programs"].get(HEAD_PROGRAM, {}).get("calls", 0)
    rows_by_kind = {k.split(".", 1)[1]: v
                    for k, v in run["probes"].counts.items()
                    if k.startswith("predict_rows.")}
    w_bytes = []
    for kind, rows in rows_by_kind.items():
        dims = flops.head_dims(mc["gtn"], mc["hidden"],
                               mc["theta_dim"][kind], mc["n_targets"])
        f += rows * flops.head_row_flops(dims)
        b += rows * flops.head_row_bytes(dims)
        w_bytes.append(flops.head_weight_bytes(dims))
    # Each dispatch reads one model's weights; the smaller set is a floor.
    return f, b + calls * (min(w_bytes) if w_bytes else 0)


def roofline_pct(run, program, work):
    if run.get("trace") is None:
        return None
    t = run["trace"]["programs"].get(program, {}).get("s", 0.0)
    f, b = work(run)
    if t <= 0 or f <= 0:
        return None
    least, _ = flops.roofline_s(f, b, run["peak"])
    return 100.0 * least / t
