"""The work of the held experts' grouped products (`moe_experts`: one
call for gate and up, one for down, a sparse layer a decode step) over
the traced steps. Bytes: each held expert that a call's tokens hit is
read once, its three matrices, plus the routed rows in and out; an expert
that no token hit need not be read. FLOPs: 6 x width x expert width an
assignment (three products of 2 x width x expert width). Counted from the
program's counters where it keeps them (`serve.moe_assignments_held`, the
sum of the group sizes it computed) and else from the expectation under
seeded weights, `top_k x held / published` assignments a token."""


def expected_hit(assignments: float, held: int) -> float:
    """Held experts hit at least once by `assignments` spread evenly."""
    return held * (1.0 - (1.0 - 1.0 / held) ** assignments)


def count(assignments: float, calls: float, d: dict, itemsize: int = 2):
    """(flops, bytes) of `calls` sparse layers' expert products (gate-up
    and down together one call) that took `assignments` held assignments
    in all, spread evenly over the calls."""
    h, im, e = d["width"], d["expert_width"], d["expert_params"]
    hit = expected_hit(assignments / max(calls, 1.0), d["experts_held"])
    # a routed row in and out (width each), gate and up out and their
    # product in again (3 x expert width)
    io = assignments * (2 * h + 3 * im) * itemsize
    return 2 * e * assignments, calls * hit * e * itemsize + io


def work(params, ctx, calls):
    """(flops, bytes) over the traced window, or nothing where no stream
    was decoded in it. The rows are the decoded tokens': a prefill's
    calls are counted (the experts they read) but not its rows, whose
    number the driver does not keep by step, so the share read is a
    little low where prompts are a large part, never high."""
    t, d = ctx.get("traced"), ctx["spec"].dims
    if not t or not t["decode_rows"]:
        return None
    n_calls = calls[params["count_by"]] / 2.0      # two products a layer
    share = held_share(ctx)
    if share is None:
        share = d["experts_held"] / d["experts_published"]
    return count(t["decode_rows"] * d["sparse_layers"] * d["top_k"] * share,
                 n_calls, d)


def held_share(ctx):
    """assignments held / (tokens routed x top_k), from the program's
    counters over the whole run; nothing where it keeps none."""
    from paddle_tpu import observability as obs

    held = obs.registry.get("serve.moe_assignments_held")
    routed = obs.registry.get("serve.moe_tokens_routed")
    if held is None or routed is None:
        return None
    owner = ctx["spec"].name
    n = routed.value(engine=owner)
    if not n:
        return None
    return held.value(engine=owner) / (n * ctx["spec"].dims["top_k"])
