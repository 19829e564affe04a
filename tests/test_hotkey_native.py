"""The hot-key detector's native pass (native/gubtpu.cpp gub_hotkey_observe,
`native.HotkeyPass`) held, bit for bit, to the numpy form `observe` ran
until PR 49 and keeps as its reference (`HotKeyTracker._sketch_numpy`:
`HostCMS.update`, then `HostCMS.estimate`, then the compare with the
admission floor).

Every case drives TWO trackers on one injected clock — one whose `observe`
takes the native pass, one built with `native.available()` patched false —
through the same sequence of batches and clock steps, and after EVERY step
compares the sketch's int64[depth, width] table word for word, the
candidate set, and what the windows made of them: `hot_set`, `version`,
`promotions`, `demotions`.  The batches lie on both sides of
`native.HOTKEY_HOLD_GIL_UP_TO`, so both bindings of the pass are held to
the reference.  No case asserts a time.
"""
import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.core.config import HotKeyConfig
from gubernator_tpu.runtime.hotkey import HotKeyTracker

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)

I64 = np.int64


def _fps(rng, n):
    """n distinct-looking non-zero fingerprints over the whole int64 range
    (the uint64 view of a negative one has its top bit set)."""
    fps = rng.integers(-(1 << 63), (1 << 63) - 1, n, dtype=I64)
    fps[fps == 0] = 1
    return fps


def _uniform(n, zeros):
    """Six batches of n fresh fingerprints, hits 0..4; with `zeros`, a zero
    fingerprint (the parser's error sentinel) in about one place in eight
    and, every third batch, in every place."""
    def steps(rng):
        for b in range(6):
            fps = _fps(rng, n)
            if zeros:
                fps[rng.random(n) < 0.125] = 0
                if b % 3 == 1:
                    fps[:] = 0
            yield "batch", fps, rng.integers(0, 5, n).astype(I64)
    return dict(steps=steps)


def _hits(kind):
    """One set of 40 fingerprints sent eight times over with hits that are
    all 0 (a read weighs 1), negative (weighs 1) or near 2^62 (the cells
    wrap as int64 does in numpy, and an estimate turns negative)."""
    def steps(rng):
        fps = _fps(rng, 40)
        for _ in range(8):
            if kind == "zero":
                hits = np.zeros(40, dtype=I64)
            elif kind == "negative":
                hits = -rng.integers(1, 1 << 40, 40).astype(I64)
            else:
                hits = rng.integers(1 << 61, 1 << 62, 40).astype(I64)
            yield "batch", rng.permutation(fps), hits
    return dict(steps=steps, threshold=40.0)


def _duplicates():
    """A key 300 times in ONE batch among 200 others: its estimate is taken
    after the whole batch's adds, so every occurrence reaches the floor —
    the first as the last — and the others do not."""
    def steps(rng):
        for _ in range(3):
            fps = _fps(rng, 500)
            fps[rng.permutation(500)[:300]] = 0x5EED5EED5EED
            yield "batch", fps, np.ones(500, dtype=I64)
            yield "tick", 0.25
    return dict(steps=steps, threshold=1000.0)   # floor 125


def _collisions(depth, width):
    """A sketch far too small for its keys: every row collides, the cells
    pass the floor (29) at different batches, and a fingerprint is a
    candidate only once its LEAST cell has."""
    def steps(rng):
        fps = _fps(rng, 64)
        for _ in range(12):
            take = rng.permutation(64)[:24]
            yield "batch", fps[take], rng.integers(0, 3, 24).astype(I64)
    return dict(steps=steps, threshold=8.0 * 29, depth=depth, width=width)


def _cap(free):
    """The candidate set `free` places under its cap of 256 when a batch
    brings five keys over the floor: it takes `free` of them in batch
    order, and at the cap no estimate is made and the table still counts."""
    def steps(rng):
        yield "fill", 256 - free
        fps = _fps(rng, 5)
        for _ in range(2):
            yield "batch", np.repeat(fps, 3), np.full(15, 50, dtype=I64)
    return dict(steps=steps, threshold=8.0)      # floor 1


def _lifecycle():
    """promote -> roll -> demote: two keys hot for four windows under
    measured pressure, one of them and then neither after, idle windows at
    the end — the tracker's whole public state, window for window."""
    def steps(rng):
        a, b = 0x0A0A0A0A0A0A, -0x0B0B0B0B0B0B
        cold = _fps(rng, 300)
        for w in range(12):
            for _ in range(4):
                fps = rng.permutation(cold)[:30]
                hits = np.ones(30, dtype=I64)
                if w < 4:
                    fps[:2], hits[:2] = (a, b), 100
                elif w < 7:
                    fps[0], hits[0] = a, 100
                yield "batch", fps, hits
                yield "tick", 0.25
        yield "tick", 3.0
        yield "poll",
    return dict(steps=steps, threshold=200.0, promote_windows=2,
                demote_windows=2, lifecycle=True)


CASES = {
    **{f"uniform-{n}{'-zeros' if z else ''}": _uniform(n, z)
       for n in (1, 2, 16, 750, 5000) for z in (False, True)},
    "hits-zero": _hits("zero"),
    "hits-negative": _hits("negative"),
    "hits-large": _hits("large"),
    "duplicates-in-a-batch": _duplicates(),
    "collisions-depth4-width8": _collisions(4, 8),
    "collisions-depth1-width2": _collisions(1, 2),
    "collisions-depth6-width16": _collisions(6, 16),
    "candidates-at-the-cap": _cap(0),
    "candidates-one-under-the-cap": _cap(1),
    "promote-roll-demote": _lifecycle(),
}


def _state(tr):
    return (set(tr._cand), tr.hot_set, tr.version, tr.promotions,
            tr.demotions)


@pytest.mark.parametrize("case", CASES)
def test_native_pass_is_the_numpy_form_bit_for_bit(case, monkeypatch):
    spec = dict(CASES[case])
    steps = spec.pop("steps")
    lifecycle = spec.pop("lifecycle", False)
    dims = {k: spec.pop(k) for k in ("depth", "width") if k in spec}
    cfg = HotKeyConfig(**{"window_s": 1.0, "max_hot": 8, **spec})
    clock = [100.0]

    def tracker():
        tr = HotKeyTracker(cfg, time_fn=lambda: clock[0], **dims)
        tr.pressure_fn = lambda fp: 1.0
        return tr

    fast = tracker()
    with monkeypatch.context() as m:
        m.setattr(native, "available", lambda: False)
        plain = tracker()
    assert fast._native_pass is not None and plain._native_pass is None

    rng = np.random.default_rng(sorted(CASES).index(case))
    candidates = batches = 0
    for step in steps(rng):
        if step[0] == "batch":
            _, fps, hits = step
            took = fast.observe(fps, hits), plain.observe(fps, hits)
            assert took == (bool(fps.any()), False)
            batches += 1
        elif step[0] == "tick":
            clock[0] += step[1]
        elif step[0] == "poll":
            fast.poll(), plain.poll()
        else:                                   # "fill"
            for tr in (fast, plain):
                tr._cand.update(range(1, step[1] + 1))
        assert np.array_equal(fast._cms.table, plain._cms.table), batches
        assert _state(fast) == _state(plain), batches
        candidates = max(candidates, len(fast._cand))
    assert batches and fast._cms.table.dtype == I64
    if case.startswith(("hits", "dup", "coll", "cand", "promote")):
        assert candidates                      # the floor was reached
    if case.startswith("candidates"):
        assert candidates == fast._cand_cap == 256
    if lifecycle:
        assert fast.promotions == 2 and fast.demotions == 2
        assert not fast.hot_set and fast.version >= 3
