"""The work of absorbed latent attention (`mla_decode`: one call a latent
layer a decode step) over the streams decoded in the traced steps (the
serve driver counts their valid lengths). What the mathematics needs,
whatever lanes the program pads a row to: every valid row read ONCE, its
`rank + rope` numbers serving as the key of every head and its first
`rank` as the value; the absorbed query in and the `rank`-wide result out.
FLOPs: a head's score is `rank + rope` multiply-adds a row and its value
`rank` more. At 128 heads, a rank of 512 and 64 rotated dims that is
128 x 1,088 x 2 FLOPs over 1,152 bytes a row, 242 FLOPs a byte: the v5e's
ridge (240.5), so which bound sets the least time is decided by the
query's and the result's bytes."""


def count(sum_lens: int, rows: int, heads: int, rank: int, rope: int,
          itemsize: int = 2):
    """(flops, bytes) of one layer over `rows` streams whose valid lengths
    sum to `sum_lens`."""
    flops = heads * (2 * rank + rope) * 2 * sum_lens
    bytes_ = (rank + rope) * itemsize * sum_lens \
        + rows * heads * (2 * rank + rope) * itemsize
    return flops, bytes_


def work(params, ctx, calls):
    """(flops, bytes) of every latent layer over the traced window, or
    nothing where no stream was decoded in it or the stack has no such
    layer."""
    t, d = ctx.get("traced"), ctx["spec"].dims
    if not t or not t["sum_ctx"] or "latent_rank" not in d:
        return None
    f, b = count(t["sum_ctx"], t["decode_rows"], d["heads"],
                 d["latent_rank"], d["rope_dim"])
    return f * d["layers"], b * d["layers"]
