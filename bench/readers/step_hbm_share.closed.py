"""step_hbm_share.closed: the least time HBM needs for the traced span's
decisions, as a share of the time the device was busy.

decisions = checks the backend counted between trace start and stop
(/debug/vars backend.checks); bytes per decision from shapes
(bench/lib/roofline.py); peak from bench/peaks.json by device kind (an
unknown device is an error, not a default).  Per chip: a mesh spreads the
decisions over its chips."""
from lib import roofline


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    flat = ctx["flat"]
    checks = flat.get("tracevars:backend.checks")
    if not trace.get("busy_s") or checks is None:
        return None
    peak = roofline.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    chips = max(1, trace.get("chips_traced", 1))
    need_s = roofline.bytes_per_decision(ctx["ways"]) * checks / chips / peak
    return need_s / trace["busy_s"] * 100.0
