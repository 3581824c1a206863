"""The work of decode attention where layers differ in what they read: a
full-attention layer reads every valid K and V row of a stream (the serve
driver's `sum_ctx` over the traced steps), a sliding-window layer the
window's rows (`rows x window`: every decoded stream of the cells that
list this metric is longer than a window), q read and out written in
both. HBM-bound. `paged_decode_roofline` counts every layer as full and
keeps its own list of cells."""
from benchmark.work import paged_decode


def work(params, ctx, calls):
    """(flops, bytes) over the traced window by kind of layer, or nothing
    where no stream was decoded in it."""
    t, d = ctx.get("traced"), ctx["spec"].dims
    if not t or not t["sum_ctx"]:
        return None
    args = (t["decode_rows"], d["heads"], d["kv_heads"], d["head_dim"])
    f_full, b_full = paged_decode.count(t["sum_ctx"], *args)
    f_win, b_win = paged_decode.count(t["decode_rows"] * d["window"], *args)
    return (f_full * d["full_layers"] + f_win * d["sliding_layers"],
            b_full * d["full_layers"] + b_win * d["sliding_layers"])
