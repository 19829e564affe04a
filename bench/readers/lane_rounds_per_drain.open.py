"""lane_rounds_per_drain.open: device rounds dispatched per coalescer drain,
both counted inside the traced span so that they cover the same time.

rounds = launches of the step programs (`spec["read"]["program_regex"]`)
on the device plane, per chip; drains = events of the host stage
`spec["read"]["drain_stage"]` (one `gub.lane.pack` a drain,
runtime/tracing.py).  1 where every drain is one round; a cascade merge's
write-back adds one, a duplicate group that holds a peek one an
occurrence.  Nothing where the program records no such stage."""
import re


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    pat = re.compile(spec["read"]["program_regex"])
    launches = sum(
        c for name, (c, _s) in trace.get("modules", {}).items()
        if pat.search(name)
    )
    drains = (trace.get("host_stages") or {}).get(
        spec["read"]["drain_stage"], [0, 0.0]
    )[0]
    if not launches or not drains:
        return None
    return launches / max(1, trace.get("chips_traced", 1)) / drains
