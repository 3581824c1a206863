"""The work of the lightning indexer (`dsa_index`: one call a sparse-
attention layer a decode step) over the streams decoded in the traced
steps (the serve harness counts their valid lengths). What the mathematics
needs: every cached index key of a stream read ONCE (`dim` numbers a
token), the `heads` index queries and their weights in; FLOPs: a product
of `dim` a head a key (the ReLU, the weighting and the sum over heads are
the vector unit's and not counted). At 32 heads of 128 that is 32 x 128 x 2
FLOPs over 256 bytes a key, 32 FLOPs a byte: far under the v5e's ridge
(240.5), so the least time is the keys' bytes."""


def count(sum_lens: int, rows: int, heads: int, dim: int, itemsize: int = 2):
    """(flops, bytes) of one layer over `rows` streams whose valid lengths
    sum to `sum_lens`: the keys and the queries in the cache's type, the
    weights in float32."""
    flops = heads * dim * 2 * sum_lens
    bytes_ = dim * itemsize * sum_lens + rows * heads * (dim * itemsize + 4)
    return flops, bytes_


def work(params, ctx, calls):
    """(flops, bytes) of every sparse-attention layer over the traced
    window, or nothing where no stream was decoded in it or the stack has
    no indexer."""
    t, d = ctx.get("traced"), ctx["spec"].dims
    if not t or not t["sum_ctx"] or "index_heads" not in d:
        return None
    f, b = count(t["sum_ctx"], t["decode_rows"], d["index_heads"],
                 d["index_dim"])
    return f * d["layers"], b * d["layers"]
