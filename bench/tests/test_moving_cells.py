"""PR 33's two deployments as data, and their rehearsals without the chip.

Both are built and judged and, by PR 33, held out of BENCHMARK.json
(bench/held_out.json).  `persec10m-1chip` (windows of a second: the clock
moves): the served program fails the moving-clock comparison, and with
the fault that shows without a chip put right underneath (the witness
`--control oneclock`) it passes here.  `token1k-1chip` (all token, windows
of 30 days: the clock stands): correct, and too unsteady on the chip for its
bound.  Every data file PR 32 left is held to its bytes.

A later PR enters either cell by appending to BENCHMARK.json and edits no
file, so nothing here asks that a cell be absent from it, every rehearsal
passes `--held-out` (which a cell of BENCHMARK.json may be given too), and
the witness is asked for only while the program as it stands fails the
rehearsal: once the program takes the clock once a merge, these tests
rehearse it as it is."""
import hashlib
import json
import os
import subprocess
import sys

import pytest
from test_dryrun import dry_run

from lib import spec

PERSEC = "persec10m.zipf99.rpc16.closed"
TOKEN1K = "token1k.batch.closed"
# sha256 of every configuration, traffic mix and per-layer metric file of
# the parent commit (PR 32): a later cell adds files and edits none.
PARENT = spec.load_json(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "parent_data_files_pr32.json"))


@pytest.mark.parametrize("path", sorted(PARENT))
def test_every_data_file_of_the_parent_is_untouched_to_its_bytes(path):
    with open(os.path.join(spec.REPO, path), "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == PARENT[path]


def test_persec10m_is_exact10m_but_for_limit_window_and_clock():
    bm = spec.benchmark(held_out=True)
    a = spec.load_json(spec.config_path(bm, "exact10m-1chip"))
    b = spec.load_json(spec.config_path(bm, "persec10m-1chip"))
    assert b["reduced"] == [] and b["daemon"] == a["daemon"]
    assert b["background_timers_s"] == a["background_timers_s"]
    ua, ub = a["universe"], dict(b["universe"])
    assert (ub.pop("limit"), ub.pop("duration_ms"), ub.pop("clock")) == (
        100, 1000, "moving")
    assert ub == {k: v for k, v in ua.items()
                  if k not in ("limit", "duration_ms")}
    assert spec.clock_moves(b["universe"])
    assert not spec.clock_moves(ua)


def test_the_held_out_cells_report_what_the_cell_they_name_reports():
    bm = spec.benchmark()
    held = spec.benchmark(held_out=True)
    spec.check_benchmark(held)
    like = "exact10m.zipf99.rpc16.closed"     # the one-chip .closed lists
    for name, traffic in ((PERSEC, "zipf99.rpc16.closed"),
                          (TOKEN1K, "batch.closed")):
        cell = spec.workload(held, name)
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert (cell["traffic"], cell["chips"]) == (traffic, 1)
        for group in ("end_to_end", "per_layer"):
            assert ([m["name"] for m in spec.metrics_of(held, group, name)]
                    == [m["name"] for m in
                        spec.metrics_of(held, group, like)])
    # Under --held-out the four-chip cells are BENCHMARK.json's and the
    # held-out ones it has not entered (the cluster's was one from PR 37 to
    # PR 39, test_cluster.py): held by membership, so that an entry or a
    # new held-out cell does not fail this.
    listed = spec.load_json(os.path.join(spec.BENCH, "held_out.json"))[
        "workloads"]
    four = {w["name"] for w in held["workloads"] if w["chips"] == 4}
    assert four == {w["name"] for w in bm["workloads"] + listed
                    if w["chips"] == 4}
    # A held-out cell reports what a cell of BENCHMARK.json with its chips
    # and its kind of loop reports.
    entered = {w["name"]: w for w in bm["workloads"]}
    for w in listed:
        assert w["held_out_because"]
        as_ = entered[w["reports_as"]]
        assert as_["chips"] == w["chips"]
        assert (as_["traffic"].rsplit(".", 1)[-1].rstrip("0123456789")
                == w["traffic"].rsplit(".", 1)[-1].rstrip("0123456789"))
    # Every cell of BENCHMARK.json reports what it reported.
    for w in bm["workloads"]:
        for group in ("end_to_end", "per_layer"):
            assert ([m["name"] for m in
                     spec.metrics_of(held, group, w["name"])]
                    == [m["name"] for m in
                        spec.metrics_of(bm, group, w["name"])])


def test_a_held_out_cell_entered_later_is_taken_from_benchmark_json(
        tmp_path, monkeypatch):
    """The PR that enters the cell appends to BENCHMARK.json and edits no
    file: bench/held_out.json still lists it, and BENCHMARK.json wins."""
    held = spec.benchmark(held_out=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(held))
    monkeypatch.setattr(spec, "REPO", str(tmp_path))
    again = spec.benchmark(held_out=True)
    assert again == held


def test_token1k_is_the_sources_all_token_universe_on_the_frozen_clock():
    bm = spec.benchmark(held_out=True)
    c = spec.load_json(spec.config_path(bm, "token1k-1chip"))
    u = c["universe"]
    assert (u["keys"], u["algorithm"], u["limit"]) == (1000, "token", 10**9)
    assert not spec.clock_moves(u) and c["reduced"] == []


@pytest.mark.parametrize("value", ["both", "leaky", "", 0, None])
def test_an_unknown_algorithm_datum_is_refused_with_the_forms_named(value):
    c = spec.load_json(spec.config_path(spec.benchmark(held_out=True),
                                        "token1k-1chip"))
    c["universe"]["algorithm"] = value
    with pytest.raises(spec.SpecError) as e:
        spec.check_config(c, "edited")
    for form in ('"mixed"', '"token"'):
        assert form in str(e.value)


def test_the_algorithm_datum_decides_every_keys_algorithm():
    import numpy as np

    from gubernator_tpu import native
    from lib import universe as U

    cfg = {"keys": 500, "ways": 8, "shards": 1, "global_keys": 0,
           "limit": 10, "duration_ms": 1000, "preload_remaining_below": 4}
    mixed = U.build_universe(native, cfg, 7, 1 << 16)
    assert 0 < int(mixed.algo.sum()) < 500
    assert (mixed.algo == ((mixed.ids >> 4) & 1)).all()
    u = U.build_universe(native, dict(cfg, algorithm="token"), 7, 1 << 16)
    assert (u.algo == U.ALGO_TOKEN).all() and (u.ids == mixed.ids).all()
    wide = U.build_universe(
        native, dict(cfg, preload_remaining_below=10**9), 7, 1 << 16)
    assert wide.remaining0.dtype == np.uint32
    assert (wide.remaining0 == mixed.ids >> 8).all()
    assert mixed.remaining0.dtype == np.uint8


# -- the rehearsals -----------------------------------------------------------

def _dry_run(tmp_path, cell, *extra, keys="39000"):
    result, failed, _ = dry_run(tmp_path, cell, *extra, "--keys", keys,
                                seconds="3")
    return result, failed


def test_a_held_out_cell_needs_asking_for(tmp_path):
    entered = {w["name"] for w in spec.benchmark()["workloads"]}
    still_out = [w["name"] for w in spec.load_json(os.path.join(
        spec.BENCH, "held_out.json"))["workloads"] if w["name"] not in entered]
    if not still_out:
        pytest.skip("every cell of bench/held_out.json has been entered")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"),
         "--workload", still_out[0], "--seed", "1", "--seconds", "1",
         "--trace", "0", "--platform", "cpu", "--slots", "65536",
         "--keys", "39000", "--out", str(tmp_path / "out")],
        cwd=spec.REPO, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode != 0 and "no workload" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


NOT_A_RESULT = {"not_a_tpu_run", "cell_held_out"}
MOVING_ZEROES = (
    "wrong_answers", "wrong_reset_time", "clock_outside_rpc",
    "clock_runs_backwards", "answered_after_expiry", "renewed_before_expiry",
    "live_window_answers_missing", "new_window_answers_missing",
    "whole_token_leaks_missing", "occupancy_below_expected",
    "occupancy_beyond_expected", "probe_differs_from_placement")


@pytest.fixture(scope="module")
def as_it_stands(tmp_path_factory):
    """The rehearsal of `persec10m` on the program as it stands, and the
    witness the other rehearsals need beside them: `oneclock` while this
    one fails (PERF.md section 7, PR 33: a cascade merge reads the clock
    again for its write-back round), nothing once it passes."""
    result, failed = _dry_run(tmp_path_factory.mktemp("stands"), PERSEC,
                              "--held-out")
    return result, failed, [] if failed == NOT_A_RESULT else ["oneclock"]


def test_persec10m_rehearses_sound_as_the_program_stands(as_it_stands):
    _, failed, witness = as_it_stands
    if witness:
        pytest.xfail("PERF.md section 7 (PR 33): a cascade merge reads the "
                     f"clock again for its write-back round: {failed}")
    assert failed == NOT_A_RESULT


def test_persec10m_rehearses_sound_on_one_clock_a_merge(tmp_path,
                                                        as_it_stands):
    """Every comparison of the moving clock reads 0, and the run met live
    windows, new ones and whole-token leaks: the program as it stands, or,
    while that fails, with the write-back round of a cascade merge under
    the clock of its read round (bench/witness/oneclock.py)."""
    result, failed, witness = as_it_stands
    if witness:
        result, failed = _dry_run(tmp_path, PERSEC, "--held-out",
                                  "--control", ",".join(witness))
    assert failed == NOT_A_RESULT
    assert result["correct"] is False and result["failed"] == 0
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    assert list(result)[-2:] == ["replay", "compared"]
    for name in MOVING_ZEROES:
        assert result["compared"][name] == [0, 0], name
    saw = result["replay"]
    assert min(saw["live_window_answers"], saw["new_window_answers"],
               saw["whole_token_leaks"]) > 1000
    assert 0 < saw["live_leaky_answers_with_room_for_a_token"] <= (
        saw["live_leaky_answers"]) < saw["live_window_answers"]


@pytest.mark.parametrize("control, must_fail", [
    ("alter", "wrong_answers"),
    ("f32", "clock_outside_rpc"),
])
def test_persec10m_with_a_broken_daemon_is_not_correct(
        tmp_path, as_it_stands, control, must_fail):
    """Each control beside the witness while the program needs one, so
    that what it reads is its own and not the program's fault."""
    result, failed = _dry_run(
        tmp_path, PERSEC, "--held-out", "--control",
        ",".join([control] + as_it_stands[2]))
    assert must_fail in failed and "wire_check_mismatches" in failed
    assert result["correct"] is False
    assert result["compared"][must_fail][0] > 100


def test_token1k_rehearses_sound(tmp_path):
    result, failed = _dry_run(tmp_path, TOKEN1K, "--held-out", keys="1000")
    assert failed == NOT_A_RESULT
    assert result["correct"] is False and result["failed"] == 0
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    assert "replay" not in result and "clock_outside_rpc" not in (
        result["compared"])


@pytest.mark.parametrize("control, must_fail", [
    ("alter", "wrong_answers"),
])
def test_token1k_with_a_broken_daemon_is_not_correct(tmp_path, control,
                                                     must_fail):
    result, failed = _dry_run(tmp_path, TOKEN1K, "--held-out",
                              "--control", control, keys="1000")
    assert must_fail in failed
    assert result["correct"] is False
