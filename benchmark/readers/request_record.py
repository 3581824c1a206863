"""The mean, in milliseconds, of `to` minus `from` (two of the times
`submit`, `admit`, `first_token`, `finish`) over the request records that
the engine itself keeps (`paddle_tpu.observability.tracing.ring(<engine's
name>, "requests")`), for the requests, warm-up ones left out, whose first
token fell in the last `window_s` seconds before the tracer stopped."""
import numpy as np

from benchmark.readers.step_record import window_records


def read(params, ctx):
    values = [r[params["to"]] - r[params["from"]]
              for r in window_records(ctx, params.get("owner"), "requests",
                                      "first_token")
              if not r["warmup"] and r[params["to"]] is not None
              and r[params["from"]] is not None]
    if not values:
        return None
    note = f"{len(values)} requests"
    ttft, late = (ctx.get("spans") or {}).get("ttft"), ctx.get("late")
    if ttft and late and len(ttft) == len(late):
        # the other side of the identity first token - due = lateness +
        # queue wait + prefill, as the harness saw it from outside, over
        # the requests DUE in its window: first token minus submit. Its
        # largest values are shown because the requests submitted as the
        # window closes wait out `stop_trace` before their prefill.
        outside = np.sort(np.asarray(ttft) - np.asarray(late)) * 1e3
        note += (f"; harness: first token minus submit over {len(ttft)} "
                 f"due in its window, mean {outside.mean():.3f} ms, "
                 f"largest {outside[-1]:.3f}, {outside[-2:-1].sum():.3f}, "
                 f"mean lateness {np.mean(late) * 1e3:.3f} ms")
    ctx.setdefault("notes", {})[params["name"]] = note
    return float(np.mean(values)) * 1e3
