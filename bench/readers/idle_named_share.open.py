"""idle_named_share.* (every kind of cell reads through this file): of the
idle-gap seconds bench/lib/trace.py lists for chip 0 (each gap named by the
shortest gub.* stage covering it, else by the most specific host event over
it), the share under the program's own stage names.

`spec["read"]["prefix"]` is the prefix those names carry ("gub.", the stage
ledger's profiler annotations, runtime/tracing.py).  A program without the
ledger (no `stages` block in /debug/vars) has no such span: nothing to
read."""


def read(ctx, spec):
    gaps = (ctx.get("trace") or {}).get("idle_gaps") or []
    snaps = ctx.get("snaps") or ({}, {})
    if "stages" not in (snaps[-1].get("vars") or {}):
        return None
    total = sum(s for _name, s in gaps)
    if not total:
        return None
    prefix = spec["read"]["prefix"]
    named = sum(s for name, s in gaps if name.startswith(prefix))
    return 100.0 * named / total
