"""The work of decode attention over the streams decoded in the traced
steps (the serve driver counts their valid lengths): every valid K and V
row read once, q read and out written. HBM-bound."""


def count(sum_lens: int, rows: int, heads: int, kv_heads: int, dh: int,
          itemsize: int = 2):
    """(flops, bytes) of one layer over `rows` streams whose valid lengths
    sum to `sum_lens`."""
    flops = 4 * heads * dh * sum_lens
    bytes_ = 2 * kv_heads * dh * itemsize * sum_lens \
        + 2 * rows * heads * dh * itemsize
    return flops, bytes_


def work(params, ctx, calls):
    """(flops, bytes) of every layer over the traced window, or nothing
    where no stream was decoded in it."""
    t, d = ctx.get("traced"), ctx["spec"].dims
    if not t or not t["sum_ctx"]:
        return None
    f, b = count(t["sum_ctx"], t["decode_rows"], d["heads"], d["kv_heads"],
                 d["head_dim"])
    return f * d["layers"], b * d["layers"]
