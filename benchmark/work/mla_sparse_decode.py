"""The work of absorbed latent attention over the SELECTED rows
(`mla_sparse_decode`: one call a sparse-attention layer a decode step)
over the streams decoded in the traced steps. A stream attends
`min(length, index_topk)` rows; the serve harness counts the decoded rows
and the sum of their lengths, so the rows attended are taken as
`min(decode_rows x index_topk, sum_ctx)`: exact where every stream holds
`index_topk` rows or more (the GLM-5 cell's shortest prompt is 3,072) and
an upper bound elsewhere. What the mathematics needs, whatever lanes the
program pads a row to: every selected row read ONCE, its `rank + rope`
numbers the key of every head and its first `rank` the value; the absorbed
query in and the `rank`-wide result out. FLOPs: `heads x (rank + rope +
rank) x 2` a row, as `work/mla_decode.py` counts them. The program's
kernel walks every page of a stream and drops the unselected scores, so
it reads every row where the algorithm needs the selected ones alone: the
share falls with the context (a fifth of the rows at the GLM-5 cell's
~10k), and says what a read of the selected rows alone would win."""


def work(params, ctx, calls):
    """(flops, bytes) of every sparse-attention layer over the traced
    window, or nothing where no stream was decoded in it or the stack has
    no indexer."""
    from benchmark.work import mla_decode

    t, d = ctx.get("traced"), ctx["spec"].dims
    if not t or not t["sum_ctx"] or "index_topk" not in d:
        return None
    rows = min(t["decode_rows"] * d["index_topk"], t["sum_ctx"])
    f, b = mla_decode.count(rows, t["decode_rows"], d["heads"],
                            d["latent_rank"], d["rope_dim"])
    return f * d["layers"], b * d["layers"]
