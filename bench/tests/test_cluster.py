"""A configuration that says `"peers": N`: the form, the ring, the universe
by owner, the summed snapshot and the plan's count of the forward hop.

Two things are held from outside the benchmark.  The copied ring
(bench/lib/ring.py) against the program's own
net/replicated_hash.ReplicatedConsistentHash, so that a change to either
side shows — ISSUE 37 asked for this test in tests/ (tier 1); the
rules this PR was built under let a `benchmark` PR add files under the
benchmark's own directories only (no test elsewhere), so it stands here,
and tier 1 does not hold the copy to the program until a later PR moves
it (PERF.md section 7).  And the universe of a configuration
WITHOUT `peers` against digests recorded from the parent commit (PR 36,
55d9b31): every array and every handoff file is what it was, bit for
bit.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest
from test_dryrun import dry_run

from lib import cluster, schedule, spec
from lib import ring as ring_mod
from lib import universe as U

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PARENT = spec.load_json(os.path.join(DATA, "parent_universe_digests_pr36.json"))
BM = spec.benchmark(held_out=True)
PEERS4 = spec.load_json(spec.config_path(BM, "peers4-10m"))
ARRAYS = ("ids", "fp", "algo", "is_global", "remaining0", "gbucket", "way",
          "resident", "crowded", "slot_order")


def native():
    from gubernator_tpu import native

    native.require()
    return native


def universe_digest(U_mod, native_mod, cfg: dict, seed: int, slots: int,
                    keys: int) -> str:
    """sha256 over every array of the universe, its handoff file and two
    occupancy reckonings (also run against the parent's checkout to
    record data/parent_universe_digests_pr36.json)."""
    uni_cfg = dict(cfg["universe"], keys=keys)
    u = U_mod.build_universe(native_mod, uni_cfg, seed, slots)
    h = hashlib.sha256()
    for name in ARRAYS:
        a = getattr(u, name)
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    hand = U_mod.handoff(u, seed, 4096)
    for name in sorted(hand):
        a = hand[name]
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    touched = np.arange(0, keys, 7)
    extra = np.array([5, -17, 1 << 40, 5], dtype=np.int64)
    h.update(str((
        u.n_resident, u.slots, u.ways, u.shards, u.moving,
        U_mod.expected_occupancy(u, touched, extra),
        U_mod.expected_occupancy(u, touched[:10], extra[:0]),
    )).encode())
    return h.hexdigest()


@pytest.mark.parametrize("key", sorted(PARENT["digests"]))
def test_without_peers_the_universe_is_the_parents_bit_for_bit(key):
    config, seed, slots, keys = key.split("/")
    cfg = spec.load_json(spec.config_path(BM, config))
    assert "peers" not in cfg
    assert universe_digest(U, native(), cfg, int(seed), int(slots),
                           int(keys)) == PARENT["digests"][key]


# -- the form ---------------------------------------------------------------

def test_the_cluster_configuration_is_valid_and_states_its_ring():
    spec.check_config(PEERS4, "peers4-10m")
    assert spec.peers_of(PEERS4) == 4 == PEERS4["chips"]
    assert len(set(spec.peer_addresses(PEERS4))) == 4
    ring = spec.ring_of(PEERS4)
    assert ring.n == 4 and ring.hash == "xx" and len(ring.points) == 4 * 512
    for name in ("exact10m-1chip", "mesh4-10m", "token1k-1chip"):
        cfg = spec.load_json(spec.config_path(BM, name))
        assert spec.peers_of(cfg) == 1 and spec.ring_of(cfg) is None


def _edited(**changes):
    c = json.loads(json.dumps(PEERS4))
    for path, value in changes.items():
        node = c
        *groups, leaf = path.split("__")
        for g in groups:
            node = node[g]
        if value is None:
            node.pop(leaf)
        else:
            node[leaf] = value
    return c


@pytest.mark.parametrize("changes", [
    {"peers": 2},                                   # not the chips
    {"peers": True},
    {"universe__shards": 4},                        # a mesh AND a ring
    {"universe__global_keys": 1024},                # GLOBAL over gRPC
    {"daemon__GUBER_PEER_PICKER_HASH": "md5"},
    {"daemon__GUBER_PEER_PICKER_HASH": "fnv1"},     # no configuration yet
    {"daemon__GUBER_PEER_PICKER_HASH": None},
    {"daemon__GUBER_PEERS": None},
    {"daemon__GUBER_PEERS": "127.0.0.1:21051,127.0.0.1:21052"},
    {"daemon__GUBER_PEERS": "127.0.0.1:21051,127.0.0.1:21051,"
                            "127.0.0.1:21053,127.0.0.1:21054"},
    {"daemon__GUBER_PEERS": "10.0.0.1:21051,127.0.0.1:21052,"
                            "127.0.0.1:21053,127.0.0.1:21054"},
    {"daemon__GUBER_PEERS": "127.0.0.1:0,127.0.0.1:21052,"
                            "127.0.0.1:21053,127.0.0.1:21054"},
], ids=lambda c: ",".join(f"{k}={v}" for k, v in c.items())[:60])
def test_a_cluster_form_outside_the_known_ones_is_refused_by_name(changes):
    with pytest.raises(spec.SpecError) as e:
        spec.check_config(_edited(**changes), "edited")
    assert "peers is none of the known forms" in str(e.value)
    assert "GUBER_PEER_PICKER_HASH" in str(e.value)


@pytest.mark.parametrize("changes", [
    {"daemon__GUBER_PEERS": "127.0.0.1:21051"},
    {"peers": 4},
])
def test_one_daemon_states_no_ring(changes):
    c = spec.load_json(spec.config_path(BM, "exact10m-1chip"))
    for path, value in changes.items():
        node = c
        *groups, leaf = path.split("__")
        for g in groups:
            node = node[g]
        node[leaf] = value
    with pytest.raises(spec.SpecError):
        spec.check_config(c, "edited")


@pytest.mark.parametrize("timeout", ["500ms", "5s", None])
def test_the_forwards_time_limit_is_the_configurations_to_state(timeout):
    """Upstream's 500 ms is what peers4-10m states; the form holds no
    configuration to another, and one that leaves it out runs the
    program's default."""
    assert PEERS4["daemon"]["GUBER_BATCH_TIMEOUT"] == "500ms"
    spec.check_config(_edited(daemon__GUBER_BATCH_TIMEOUT=timeout), "edited")


# -- the ring ---------------------------------------------------------------

@pytest.mark.parametrize("kind", ring_mod.HASHES)
@pytest.mark.parametrize("seed", [1, 2246822519])
def test_the_copied_ring_places_every_key_where_the_programs_does(kind, seed):
    from gubernator_tpu.net.replicated_hash import (
        HASH_FUNCTIONS,
        ReplicatedConsistentHash,
    )

    rng = np.random.default_rng(seed)
    addrs = [f"10.{rng.integers(256)}.{rng.integers(256)}.{rng.integers(256)}"
             f":{rng.integers(1024, 65536)}" for _ in range(5)]
    theirs = ReplicatedConsistentHash(HASH_FUNCTIONS[kind], key_of=lambda a: a)
    for a in addrs:
        theirs.add(a)
    ours = ring_mod.build(addrs, kind)
    points, peer_idx, peers = theirs.ring_arrays()
    assert peers == addrs
    assert (ours.points == points).all() and (ours.peer == peer_idx).all()
    # 100k keys of the universe: by fingerprint on an xx ring, which is
    # how the universe places them; by the bytes of the hash key,
    # "<name>_<unique_key>" (19 of them, whatever the id), on upstream's.
    ids = U.key_ids(np.arange(100_000, dtype=np.int64), seed)
    strings = [U.key_string(int(i)) for i in ids]
    if kind == "xx":
        h = U.fingerprints(native(), ids).view(np.uint64)
    else:
        h = ring_mod.hash_rows(kind, np.frombuffer(
            "".join(strings).encode(), dtype=np.uint8).reshape(len(ids), 19))
    mine = ours.owner(h)
    step = 1 if kind == "xx" else 37            # Python FNV is slow
    for i in range(0, len(ids), step):
        assert addrs[mine[i]] == theirs.get(strings[i]), i
    assert (ours.owner(ring_mod.hash_strings(kind, strings[:500]))
            == mine[:500]).all()
    # The edges of the ring: a key on a point, past the last, before the
    # first.
    edge = np.array([points[0], points[-1], points[-1] + np.uint64(1),
                     np.uint64(0), points[7] + np.uint64(1)], dtype=np.uint64)
    want = [peer_idx[0], peer_idx[-1], peer_idx[0], peer_idx[0],
            peer_idx[8] if points[8] != points[7] else peer_idx[7]]
    assert ours.owner(edge).tolist() == [int(w) for w in want]


# -- the universe by owner --------------------------------------------------

SLOTS, KEYS = 8192, 20_000      # a daemon's slots: crowded buckets exist


@pytest.fixture(scope="module")
def placed():
    ring = spec.ring_of(PEERS4)
    uni_cfg = dict(PEERS4["universe"], keys=KEYS)
    u = U.build_universe(native(), uni_cfg, 2246822519, SLOTS, ring)
    return ring, u


def test_four_peers_against_a_brute_force_placement(placed):
    ring, u = placed
    nb = SLOTS // u.ways
    assert u.slots == 4 * SLOTS and u.peers == 4
    arrivals = {}
    for i in range(KEYS):
        h = int(u.fp[i]) & 0xFFFFFFFFFFFFFFFF
        at = int(np.searchsorted(ring.points, np.uint64(h), side="left"))
        owner = int(ring.peer[at % len(ring.points)])
        bucket = owner * nb + (h & (nb - 1))
        assert (int(u.owner[i]), int(u.gbucket[i])) == (owner, bucket)
        way = arrivals.get(bucket, 0)
        arrivals[bucket] = way + 1
        assert int(u.way[i]) == way and bool(u.resident[i]) == (way < 8)
    for i in range(KEYS):
        assert bool(u.crowded[i]) == (arrivals[int(u.gbucket[i])] > 8)
    assert u.crowded.any() and not u.resident.all()
    assert u.n_resident == sum(min(c, 8) for c in arrivals.values())
    by_daemon = u.resident_by_daemon()
    assert by_daemon.sum() == u.n_resident and (by_daemon > 0).all()
    # Every peer a fair share of the ring (512 vnodes a peer).
    share = np.bincount(u.owner, minlength=4) / KEYS
    assert share.min() > 0.2 and share.max() < 0.3


def test_expected_occupancy_sums_over_the_daemons(placed):
    ring, u = placed
    nb = u.slots // u.ways
    touched = np.flatnonzero(~u.resident)[:300]
    aux = np.array([77, -5, 1 << 50, 77], dtype=np.int64)
    counts = np.zeros(nb, dtype=np.int64)
    np.add.at(counts, u.gbucket[u.resident], 1)
    np.add.at(counts, u.gbucket[touched], 1)
    for f in set(aux.tolist()):
        h = f & 0xFFFFFFFFFFFFFFFF
        at = int(np.searchsorted(ring.points, np.uint64(h), side="left"))
        owner = int(ring.peer[at % len(ring.points)])
        counts[owner * (nb // 4) + (h & (nb // 4 - 1))] += 1
    assert U.expected_occupancy(u, touched, aux) == int(
        np.minimum(counts, 8).sum())
    assert (u.bucket_of(u.fp[:1000]) == u.gbucket[:1000]).all()


def test_a_daemons_handoff_holds_its_own_rows_at_its_own_slots(placed):
    _, u = placed
    seen = 0
    for d in range(4):
        h = U.handoff(u, 7, 4096, d)
        assert h["geometry"].tolist()[0] == SLOTS
        assert len(np.unique(h["slot"])) == len(h["slot"])
        assert h["slot"].min() >= 0 and h["slot"].max() < SLOTS
        assert (np.diff(h["slot"]) > 0).all()           # slot order
        mine = u.resident & (u.owner == d)
        assert len(h["fp"]) == mine.sum() == u.resident_by_daemon()[d]
        assert set(h["fp"].tolist()) == set(u.fp[mine].tolist())
        # The whole sample, found only where resident AND owned here.
        assert len(h["probe_fp"]) == 4096
        owned = np.isin(h["probe_fp"], u.fp[u.owner == d])
        assert not (h["probe_found"] & ~owned).any()
        assert h["probe_found"].any() and (owned & ~h["probe_found"]).any()
        rows = U.table_arrays(h, 1_700_000_000_000)
        assert (rows["key"] != 0).sum() == len(h["fp"])
        # A row sits in the bucket the daemon's own lookup will search.
        at = np.flatnonzero(rows["key"])
        assert ((rows["key"][at].view(np.uint64)
                 & np.uint64(SLOTS // 8 - 1)) == (at // 8)).all()
        seen += len(h["fp"])
    assert seen == u.n_resident


# -- one deployment, one snapshot -------------------------------------------

def test_the_summed_snapshot():
    a = {"backend": {"occupancy": 10, "checks": 5, "not_persisted": 0},
         "fastpath": {"served": 7, "serve_mode": "pipelined",
                      "lanes": {"mach": {"drains": 2}}},
         "device": {"compiled_lane": True, "table_device_ids": [0]},
         "stages": {"mach": {"pack": {"count": 2, "ms_total": 3.0,
                                      "ms_max": 2.5}}}}
    b = {"backend": {"occupancy": 4, "checks": 1, "not_persisted": 2},
         "fastpath": {"served": 1, "serve_mode": "pipelined",
                      "lanes": {"mach": {"drains": 1}, "engine": {"drains": 9}}},
         "device": {"compiled_lane": False, "table_device_ids": [0]},
         "stages": {"mach": {"pack": {"count": 1, "ms_total": 9.0,
                                      "ms_max": 9.0},
                             "cascade": {"count": 1, "ms_total": 1.0,
                                         "ms_max": 1.0, "occ": 4}}}}
    s = cluster.sum_vars([a, b])
    assert s["backend"] == {"occupancy": 14, "checks": 6, "not_persisted": 2}
    assert s["fastpath"]["served"] == 8
    assert s["fastpath"]["serve_mode"] == "pipelined"
    assert s["fastpath"]["lanes"] == {"mach": {"drains": 3},
                                      "engine": {"drains": 9}}
    assert s["stages"]["mach"]["pack"] == {"count": 3, "ms_total": 12.0,
                                           "ms_max": 9.0}
    assert s["stages"]["mach"]["cascade"]["occ"] == 4
    assert s["device"]["compiled_lane"] is True     # the first daemon's:
    assert cluster.sum_vars([a]) == a               # ask each, not the sum
    # A ratio of two sums is the cluster's mean, as a data file reads it.
    from lib import readers

    m = {"read": {"kind": "ratio", "delta": True,
                  "num": ["vars:stages.*.pack.ms_total"],
                  "den": ["vars:stages.*.pack.count"]}}
    zero = cluster.sum_vars([
        {"stages": {"mach": {"pack": {"count": 0, "ms_total": 0.0}}}}] * 2)
    assert readers.evaluate(m, {"snaps": ({"vars": zero}, {"vars": s})}) == 4.0


def test_the_plans_count_of_the_forward_hop():
    t = spec.load_json(spec.traffic_path("batch.closed"))
    uni_cfg = dict(PEERS4["universe"], keys=KEYS)
    plan = schedule.build_plan(t, uni_cfg, 3, 2.0)
    rng = np.random.default_rng(5)
    owner = rng.integers(0, 4, KEYS).astype(np.uint8)
    sent = rng.integers(0, len(plan), 200)
    entry = np.arange(200) % 4
    hops = cluster.planned_hops(plan, owner, 4, sent, entry)
    local, forward = [0] * 4, [0] * 4
    for j, e in zip(sent.tolist(), entry.tolist()):
        keys = plan.key_index[plan.offsets[j]:plan.offsets[j + 1]]
        mine = int((owner[keys] == e).sum())
        local[e] += mine
        forward[e] += len(keys) - mine
    assert hops == {"local": local, "forward": forward}
    assert sum(local) + sum(forward) == int(
        np.diff(plan.offsets)[sent].sum())


def test_several_traces_reduce_to_their_mean_daemon():
    one = {"chips_traced": 1, "busy_s": 0.4, "window_s": 2.0,
           "collective_s": 0.0, "modules": {"jit_step": [10, 0.3]},
           "host_stages": {"gub.lane.pack": [5, 0.1]},
           "device_ops": [["fusion.1", 0.2]], "idle_gaps": [["gub.x", 1.0]]}
    two = {"chips_traced": 1, "busy_s": 0.8, "window_s": 2.2,
           "collective_s": 0.0, "modules": {"jit_step": [30, 0.7]},
           "host_stages": {"gub.lane.pack": [15, 0.3]},
           "device_ops": [["fusion.1", 0.6]], "idle_gaps": [["gub.y", 2.0]]}
    idle = {**one, "chips_traced": 0, "busy_s": 0.0, "window_s": 0.0,
            "modules": {}, "host_stages": {}, "device_ops": []}
    c = cluster.combine_traces([one, two, idle])
    assert c["chips_traced"] == 1 and c["daemons_traced"] == 2
    assert c["busy_s"] == pytest.approx(0.6)
    assert c["window_s"] == pytest.approx(2.1)
    assert 1 - c["busy_s"] / c["window_s"] == pytest.approx(1 - 1.2 / 4.2)
    assert c["modules"]["jit_step"] == pytest.approx([20, 0.5])
    assert c["host_stages"]["gub.lane.pack"] == pytest.approx([10, 0.2])
    assert c["busy_s_by_chip"] == [0.4, 0.8, 0.0]
    assert c["idle_gaps"] == [["gub.x", 1.0]]
    # The readers' arithmetic on it: busy per launch, launches per drain.
    assert c["busy_s"] / (c["modules"]["jit_step"][0] / c["chips_traced"]) \
        == pytest.approx(0.03)
    assert c["modules"]["jit_step"][0] / c["host_stages"]["gub.lane.pack"][0] \
        == pytest.approx(2.0)


def test_the_pin_is_one_chip_of_the_host():
    env = cluster.pin_env(2, 8476)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_PORT"] == "8476"


# -- the rest of a run, four CPU daemons ------------------------------------

CELL = "peers4-10m.batch.closed"


def test_the_cluster_cell_is_built_and_held_out():
    # PR 37 built the cell and held it out; PR 39 entered it.  held_out.json
    # still lists it and is not edited, BENCHMARK.json wins
    # (test_peers_entered.py): whichever holds, the cell is built.
    held = spec.load_json(os.path.join(spec.BENCH, "held_out.json"))
    (w,) = [w for w in held["workloads"] if w["name"] == CELL]
    assert w["chips"] == 4 and w["reports_as"] == "mesh4-10m.batch.closed"
    assert len(w["why"]) <= 200
    spec.check_benchmark(BM)


def dry_cluster():
    """Arguments of a cluster's dry run in a test.  Its peers on ports
    nobody holds, so that two checkouts' suites on one host do not meet on
    the configuration's (the ring is then this run's own; the counts
    asserted do not depend on the placement).  And a forward's time limit
    above a stall of this host's CPU: four daemons on a few shared cores
    stall for longer than 500 ms now and then, a property of the rehearsal
    and not of the program; the chip runs keep the file's 500 ms."""
    import socket

    socks = [socket.socket() for _ in range(4)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]     # four held at once
    for s in socks:
        s.close()
    return ("--held-out", "--daemon", "GUBER_BATCH_TIMEOUT=5s", "--daemon",
            "GUBER_PEERS=" + ",".join(f"127.0.0.1:{p}" for p in ports))


NOT_A_RUN = {"not_a_tpu_run", "cell_held_out", "daemon_setting_overridden"}


def test_the_cluster_cell_rehearses_sound(tmp_path):
    result, failed, compared = dry_run(tmp_path, CELL, *dry_cluster(), seconds="3")
    assert failed == NOT_A_RUN
    assert {"forwarded_checks_differ", "local_checks_differ",
            "occupancy_beyond_expected", "preload_occupancy_differs",
            "probe_differs_from_placement", "wrong_answers"} <= compared
    assert result["compared"]["forwarded_checks_differ"] == [0, 0]
    assert result["compared"]["local_checks_differ"] == [0, 0]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert list(result)[-2:] == ["daemons", "compared"]
    d = result["daemons"]
    assert len({x["grpc"] for x in d}) == 4
    assert not {x["grpc"] for x in d} & set(spec.peer_addresses(PEERS4))
    # Three quarters of the checks crossed to their owner, and every
    # daemon served its quarter of all of them.
    forward, local = (sum(x[k] for x in d) for k in ("forward", "local"))
    assert 0.7 < forward / (forward + local) < 0.8
    assert all(x["forward"] > 0 and x["local"] > 0 for x in d)
    assert sum(x["served"] for x in d) >= forward + local
    served = [x["served"] for x in d]
    assert max(served) < 1.3 * min(served)
    assert sum(x["preloaded"] for x in d) <= sum(x["occupancy"] for x in d)


def test_the_cluster_cell_with_an_answer_altered_is_not_correct(tmp_path):
    result, failed, _ = dry_run(tmp_path, CELL, *dry_cluster(),
                                "--control", "alter", seconds="3")
    assert "wrong_answers" in failed and "wire_check_mismatches" in failed
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"][0] > 0
    # The hop itself was sound: the fault is in the answers.
    assert result["compared"]["forwarded_checks_differ"] == [0, 0]


def test_a_cluster_that_serves_all_where_it_arrives_is_not_correct(tmp_path):
    """Every daemon with a ring of itself alone: nothing is forwarded, and
    the hop's two comparisons see it — three quarters of the checks were
    the plan's to forward and none was."""
    result, failed, _ = dry_run(tmp_path, CELL, *dry_cluster(),
                                "--control", "noforward", seconds="3")
    assert {"forwarded_checks_differ", "local_checks_differ"} <= failed
    assert result["correct"] is False
    differ, limit = result["compared"]["forwarded_checks_differ"]
    assert limit == 0 and differ > 0.7 * result["attempted"]
    assert sum(x["forward"] for x in result["daemons"]) == 0


def test_the_configurations_ports_taken_is_a_run_refused(tmp_path):
    import socket
    import subprocess

    host, _, port = spec.peer_addresses(PEERS4)[2].rpartition(":")
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind((host, int(port)))
        except OSError:
            pytest.skip("another run holds the configuration's ports")
        s.listen()
        p = subprocess.run(
            [sys.executable, os.path.join(spec.BENCH, "run.py"),
             "--workload", CELL, "--held-out", "--seed", "1", "--seconds",
             "2", "--platform", "cpu", "--slots", "65536", "--keys", "39000",
             "--out", str(tmp_path / "out")],
            cwd=spec.REPO, capture_output=True, text=True, timeout=120,
        )
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert f"peer address {host}:{port} is taken" in p.stderr


if __name__ == "__main__":
    # Record the parent's digests: python test_cluster.py <parent checkout>
    parent = sys.argv[1]
    sys.path[:0] = [os.path.join(parent, "bench"), parent]
    for m in [k for k in sys.modules if k == "lib" or k.startswith("lib.")]:
        del sys.modules[m]
    from lib import universe as parent_U

    assert parent_U.__file__.startswith(parent), parent_U.__file__
    out = {}
    for config, slots, keys in (
        ("exact10m-1chip", 65536, 39000), ("mesh4-10m", 65536, 39000),
        ("mesh4-global8k", 1048576, 400000), ("persec10m-1chip", 65536, 39000),
        ("token1k-1chip", 65536, 1000), ("exact10m-1chip", 16384, 20000),
    ):
        cfg = spec.load_json(spec.config_path(BM, config))
        for seed in (1, 2246822519, 2147483655):
            out[f"{config}/{seed}/{slots}/{keys}"] = universe_digest(
                parent_U, native(), cfg, seed, slots, keys)
    json.dump({
        "what": "sha256 of every array of build_universe, its handoff "
        "file and two expected_occupancy reckonings (test_cluster.py "
        "universe_digest), recorded from the parent of PR 37 (commit "
        "55d9b31) before the universe learnt a ring; key: "
        "config/seed/slots/keys",
        "digests": out,
    }, open(os.path.join(DATA, "parent_universe_digests_pr36.json"), "w"),
        indent=1)
