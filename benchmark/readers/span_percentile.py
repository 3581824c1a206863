"""A percentile, in milliseconds, of the harness's own spans named
`span` (its clock round every call of that name inside the window)."""
import numpy as np


def read(params, ctx):
    spans = (ctx.get("spans") or {}).get(params["span"])
    if not spans:
        return None
    return float(np.percentile(np.asarray(spans), params["q"])) * 1e3
