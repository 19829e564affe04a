"""step_device_ms.closed: device busy time per step program launched.

`ctx["trace"]` is bench/lib/trace.py's reduction of the traced span;
`spec["read"]["program_regex"]` names the step programs by their jitted
names, so a renamed kernel needs a new data file and no new code."""
import re


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    pat = re.compile(spec["read"]["program_regex"])
    launches = sum(
        c for name, (c, _s) in trace.get("modules", {}).items()
        if pat.search(name)
    )
    chips = max(1, trace.get("chips_traced", 1))
    if not launches or not trace.get("busy_s"):
        return None
    # `modules` counts launches on every chip; a step runs on all of them.
    return trace["busy_s"] / (launches / chips) * 1e3
