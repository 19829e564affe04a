"""tier_inject_device_ms.closed: device time of ONE launch of the promote
path's program, counted inside the traced span.

`spec["read"]["program_regex"]` names the program among the trace's
programs (bench/lib/trace.py `modules`: launches on all chips, seconds a
chip).  Nothing where the span held no launch of it.  (The demoter's
program launches once in about 3 s, a span lasts 2: it has no such metric,
PERF.md section 7.)"""
from lib.global_sync import sync_rows


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    launches, seconds = sync_rows(trace, spec["read"]["program_regex"])
    if not launches or not seconds:
        return None
    chips = max(1, trace.get("chips_traced", 1))
    return seconds / (launches / chips) * 1e3
