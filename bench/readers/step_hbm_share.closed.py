"""step_hbm_share.closed: the least time HBM needs for what the traced
span's step programs move, as a share of the time the device was busy.

bytes = the compiler's own `bytes accessed` of the step program at the
smallest tier (bench/serve.py reads it from the compiled program at set-up
of a traced run; a launch at a larger tier moves no less) x step launches
per chip in the span (`spec["read"]["program_regex"]` names the step
programs); peak from bench/peaks.json by device kind (an unknown device is
an error, not a default).  Nothing where the daemon could not read the
bytes or nothing was traced."""
import re

from lib import roofline


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    step = ctx.get("step_bytes") or {}
    if not trace.get("busy_s") or not step.get("bytes_accessed"):
        return None
    pat = re.compile(spec["read"]["program_regex"])
    launches = sum(
        c for name, (c, _s) in trace.get("modules", {}).items()
        if pat.search(name)
    )
    if not launches:
        return None
    peak = roofline.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    chips = max(1, trace.get("chips_traced", 1))
    need_s = step["bytes_accessed"] * (launches / chips) / peak
    return need_s / trace["busy_s"] * 100.0
