"""The work of the recurrent step of the state-space layers (`ssm_decode`:
one call a Mamba layer a decode step) over the traced steps. For each
decoded row and layer the state is read and written once (`ssm_state_elems`
numbers of `state_itemsize` bytes, twice), and the row's x, B, C and dt come
in and its y goes out; a slot that decodes nothing in a step need not be
touched. FLOPs: 6 an element of the state (the decay's product, the outer
product added, the read-out's multiply and add). HBM-bound by two orders of
magnitude. Counted from the serve driver's decoded rows and the stack's
`dims`, whatever implements the kernel."""


def count(rows: int, d: dict, itemsize: int = 2):
    """(flops, bytes) of one layer's step over `rows` decoded rows."""
    inner = d["ssm_heads"] * d["ssm_head_dim"]
    state = d["ssm_state_elems"]
    # x in and y out (inner each), B and C (state size each) in the
    # activations' type; dt a float32 a head
    io = (2 * inner + 2 * d["ssm_state"]) * itemsize + 4 * d["ssm_heads"]
    return (6 * state * rows,
            rows * (2 * state * d["state_itemsize"] + io))


def work(params, ctx, calls):
    """(flops, bytes) over the traced window, or nothing where no stream
    was decoded in it."""
    t, d = ctx.get("traced"), ctx["spec"].dims
    if not t or not t["decode_rows"] or not d.get("ssm_layers"):
        return None
    flops, nbytes = count(t["decode_rows"], d)
    return flops * d["ssm_layers"], nbytes * d["ssm_layers"]
