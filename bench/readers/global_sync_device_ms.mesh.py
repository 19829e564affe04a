"""global_sync_device_ms.mesh: device time of the GLOBAL sync program per
sync tick, both counted inside the traced span.

`spec["read"]["program_regex"]` names the sync program (the shard_map
body `_global_sync` of parallel/global_sync.py) among the trace's programs,
`tick_stage` the host event one tick leaves.  Nothing where the program
runs its sync under the serve step's name (before PR 28) or no tick fell
inside the span."""
from lib.global_sync import sync_rows


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    launches, seconds = sync_rows(trace, spec["read"]["program_regex"])
    ticks = (trace.get("host_stages") or {}).get(
        spec["read"]["tick_stage"], [0, 0.0]
    )[0]
    if not launches or not ticks:
        return None
    return seconds / ticks * 1e3
