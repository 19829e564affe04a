"""global_sync_roofline_share.mesh: the least time one launch of the GLOBAL
sync program needs, as a share of the device time a launch took in the
traced span.

HBM: the compiled program's own `bytes accessed` for one device, which
the program reads from the compiler at warm-up and shows in /debug/vars
(`global.engine.sync_program.bytes_accessed`), over peak HBM bytes/s.
ICI: bench/lib/global_sync.py `ici_bytes` over peak ICI bytes/s.  The
binding one of the two is the roofline (`spec["read"]["bound"]` names it:
memory, by three orders of magnitude at this geometry); peaks from
bench/peaks.json by device kind.  Nothing where the program does not
describe its sync program (before PR 28), the compiler gave no count, or
no launch was traced."""
from lib import readers, roofline
from lib.global_sync import ici_bytes, sync_rows


def read(ctx, spec):
    trace = ctx.get("trace") or {}
    snaps = ctx.get("snaps") or ({},)
    prog = "global.engine.sync_program."
    got = {k: readers.lookup_vars(snaps[-1].get("vars") or {}, prog + k)
           for k in ("bytes_accessed", "shards", "delta_slots")}
    r = spec["read"]
    launches, seconds = sync_rows(trace, r["program_regex"])
    if None in got.values() or not launches or not seconds:
        return None
    peaks = roofline.peaks(ctx["device"]["kind"])
    need_s = max(
        got["bytes_accessed"] / peaks["hbm_bytes_per_s"],
        ici_bytes(int(got["shards"]), int(got["delta_slots"]),
                  r["psum_words_per_lane"], r["gather_bytes_per_lane"])
        / (peaks["ici_bits_per_s"] / 8),
    )
    chips = max(1, trace.get("chips_traced", 1))
    return need_s / (seconds / (launches / chips)) * 100.0
