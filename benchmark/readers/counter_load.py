"""How unevenly a step's tokens fall on the experts a chip holds: the
largest held expert's tokens a decode step over the mean held expert's,
from the program's own counters (`max` and `sum` name them; one series a
sparse layer under the engine's name, which the harness makes the
cell's). Both counters add up over the run's decode steps, so this is
the ratio of the sums, the steps weighed by their tokens: 1 is even, the
number of held experts is all on one. A program that keeps no such
counter (the parent of the PR that brought them) gives nothing to read."""


def read(params, ctx):
    from paddle_tpu import observability as obs

    biggest = obs.registry.get(params["max"])
    total = obs.registry.get(params["sum"])
    if biggest is None or total is None:
        return None
    owner = ctx["spec"].name
    mine = [ls for ls in total.labelsets() if ls.get("engine") == owner]
    held = ctx["spec"].dims[params["experts"]]
    top = sum(biggest.value(**ls) for ls in mine)
    mean = sum(total.value(**ls) for ls in mine) / held
    if not mean:
        return None
    note = (f"{len(mine)} sparse layers, {held} held experts; tokens on "
            f"them a step and layer: largest {top:.0f}, mean {mean:.1f} "
            f"(sums)")
    routed = obs.registry.get(params.get("routed", ""))
    if routed is not None and routed.value(engine=owner):
        # the share of all assignments that lands on this chip's experts
        k = ctx["spec"].dims[params["top_k"]]
        note += (f"; held {mean * held:.0f} of {routed.value(engine=owner)}"
                 f" x {k} assignments routed: "
                 f"{mean * held / (routed.value(engine=owner) * k):.4f}")
    ctx.setdefault("notes", {})[params["name"]] = note
    return top / mean
