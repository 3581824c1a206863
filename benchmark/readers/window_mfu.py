"""The whole serve step's share of the peak over the window: model FLOPs
of every prompt and output token computed in the window (true lengths;
the harness counts them step by step) over window time times the peak."""


def read(params, ctx):
    flops = ctx.get("model_flops")
    if not flops:
        return None
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
