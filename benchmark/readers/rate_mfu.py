"""The whole train step's share of the chips' bf16 peak: model FLOPs a
token (the forward pass as the configuration's stack counts it, times 3;
recomputation not counted) times tokens a second, over chips times the
peak."""
from benchmark.harness import counts


def read(params, ctx):
    if "tokens_per_s" not in ctx:
        return None
    sp = ctx["spec"]
    seq = sp.cell["seq_len"]
    per_token = counts.train_flops_per_token(
        sp.stack.forward_flops(sp.config, seq), seq)
    return 100.0 * per_token * ctx["tokens_per_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
