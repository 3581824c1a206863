"""The work of one layer's causal attention in training, forward and
backward together, at the cell's batch and sequence and the stack's heads;
one unit of work per event of the metric's `count_by` kernel."""


def count(b: int, heads: int, kv_heads: int, s: int, dh: int,
          itemsize: int = 2):
    """(flops, bytes). Forward: QK^T and PV over the causal half.
    Backward: the recomputed scores and dV, dP, dQ, dK: 2.5 x forward.
    Bytes: q, k, v, out read or written once forward; q, k, v, out, dout
    read and dq, dk, dv written once backward (the row statistics are
    small beside them)."""
    fwd = 4 * b * heads * s * s * dh / 2
    q = b * heads * s * dh * itemsize
    kv = b * kv_heads * s * dh * itemsize
    return 3.5 * fwd, (2 * q + 2 * kv) + (4 * q + 4 * kv)


def work(params, ctx, calls):
    """(flops, bytes) on one chip over the traced window."""
    sp, d = ctx["spec"], ctx["spec"].dims
    f, b = count(sp.cell["batch"], d["heads"], d["kv_heads"],
                 sp.cell["seq_len"], d["head_dim"])
    units = calls[params["count_by"]]
    return f * units / ctx["chips"], b * units / ctx["chips"]
