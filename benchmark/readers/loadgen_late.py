"""How late the generator ran: a percentile, in milliseconds, over the
open loop's requests due in the window of submit time minus due time."""
import numpy as np


def read(params, ctx):
    late = ctx.get("late")
    if not late:
        return None
    return float(np.percentile(np.asarray(late), params["q"])) * 1e3
