"""tier_inject_roofline_share.closed: the least time HBM needs for the rows
the traced span's `migrate_inject` launches carried, as a share of the
device time those launches took.

bytes = rows x what one injected row must move (`inject_bytes`: the key
looked up among its ways, its row read and written — an upsert writes it,
a merge reads and writes it; the widths step_hbm_share.closed states, from
`spec["read"]`) — counted from the work, never from the compiled program,
so the count reads the same whatever implements the kernel.  rows = rows
a launch from the program's counters over the window (`read.rows` over
`read.launches`: the ledger's tier.promote row, counted where the launches
are made) x the launches of the traced span (`read.program_regex` among
bench/lib/trace.py's `modules`).  Peak from bench/peaks.json by device
kind.  Nothing where the program has no such counters (the parent), the
window launched nothing or the span held no launch."""
from lib import readers, roofline
from lib.global_sync import sync_rows


def inject_bytes(rows: float, read: dict) -> float:
    """HBM bytes `rows` injected rows need: each finds its key among its
    ways, reads the row it meets and writes the row it leaves."""
    return rows * (read["lookup_bytes_per_lane"] + 2 * read["row_bytes"])


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    r = spec["read"]
    launches, seconds = sync_rows(trace, r["program_regex"])
    snaps = ctx.get("snaps") or ({}, {})
    rows = readers.window_delta(r["rows"], snaps)
    counted = readers.window_delta(r["launches"], snaps)
    if not launches or not seconds or not rows or not counted:
        return None
    peak = roofline.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    chips = max(1, trace.get("chips_traced", 1))
    need_s = inject_bytes(rows / counted * launches / chips, r) / peak
    return need_s / seconds * 100.0
