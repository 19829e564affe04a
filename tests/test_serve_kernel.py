"""The persistent Pallas decision kernel (ops/pallas/serve_kernel.py).

Differential pins: the interpret-mode kernel must match `ring_step`
BIT-EXACTLY (every table leaf, every response column, the sequence
word) — the decision body is inherited from apply_batch_packed_q_impl,
so any divergence is a queue/grid-plumbing bug.  Capability reporting
must be honest: CPU reports interpret-only, a backend without the
kernel reports why, and GUBER_SERVE_MODE=persistent degrades to
megaround with the reason surfaced in /debug/vars (docs/ring.md).
"""
from __future__ import annotations

import numpy as np
import pytest

from gubernator_tpu.core.config import Config, DeviceConfig
from gubernator_tpu.core.types import Algorithm, RateLimitReq
from gubernator_tpu.ops.batch import pack_requests
from gubernator_tpu.runtime.backend import DeviceBackend, pack_batch_q

DEV = DeviceConfig(num_slots=1024, ways=8, batch_size=64)


def _reqs(step: int, n: int = 10):
    return [
        RateLimitReq(
            name="pk",
            unique_key=f"k{(step * 3 + i) % 7}",
            hits=1 + (i % 2),
            limit=40,
            duration=60_000,
            algorithm=(
                Algorithm.LEAKY_BUCKET if i % 3 == 0
                else Algorithm.TOKEN_BUCKET
            ),
        )
        for i in range(n)
    ]


def _packed_qs(frozen_clock, steps=4):
    qs = []
    for s in range(steps):
        for db in pack_requests(
            _reqs(s), DEV.batch_size, frozen_clock
        ).rounds:
            qs.append(pack_batch_q(db))
    return np.stack(qs).astype(np.int64)


def test_persistent_matches_ring_step_bit_exact(frozen_clock):
    """One kernel launch draining k rounds == the ring scan: table
    leaves, packed responses, and the sequence word all bit-identical,
    including across SUCCESSIVE launches threading (table, seq)."""
    import jax.numpy as jnp

    from gubernator_tpu.ops.pallas.serve_kernel import (
        persistent_serve_step_impl,
    )
    from gubernator_tpu.ops.ring import ring_step
    from gubernator_tpu.ops.state import init_table

    qs = _packed_qs(frozen_clock)
    k = qs.shape[0]
    now = np.int64(frozen_clock.millisecond_now())
    nows = np.full(k, now, dtype=np.int64)

    rt, rresp, rseq = init_table(DEV.num_slots), None, jnp.zeros(
        (), jnp.int64
    )
    pt, presp, pseq = init_table(DEV.num_slots), None, jnp.zeros(
        (), jnp.int64
    )
    # Two launches over the same queue: the second observes the
    # first's table — the carry across launches must match too.
    for _ in range(2):
        rt, rresp, rseq = ring_step(rt, qs, nows, rseq, ways=8)
        pt, presp, pseq = persistent_serve_step_impl(
            pt, qs, nows, pseq, ways=8, interpret=True
        )
        for f, a, b in zip(rt._fields, rt, pt):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f
            )
        np.testing.assert_array_equal(
            np.asarray(rresp), np.asarray(presp)
        )
        assert int(rseq) == int(pseq)
    assert int(pseq) == 2 * k


def test_capability_reporting_is_honest():
    """CPU must NOT claim persistent support (an emulated 'persistent'
    mode would be slower than the scan it replaces): the report names
    the platform and the interpret gap.  The forced-interpret test
    seam reports itself as exactly that."""
    from gubernator_tpu.ops.pallas.serve_kernel import (
        persistent_supported,
    )

    ok, reason = persistent_supported("cpu", 256, 8, 8)
    assert not ok
    assert "cpu" in reason and "interpret" in reason

    be = DeviceBackend(DEV)
    ok, reason = be.persistent_serve_supported()
    assert not ok and "TPU" in reason

    be._persistent_interpret = True
    ok, reason = be.persistent_serve_supported()
    assert ok and "interpret" in reason


def test_persistent_ring_serving_interpret(frozen_clock):
    """The full serving path through the runner with the persistent
    kernel armed (forced interpret): submitted merges publish
    responses bit-identical to the classic dispatch, sequence word
    mirror-consistent."""
    from gubernator_tpu.runtime.ring import RingBackend

    classic = DeviceBackend(DEV, clock=frozen_clock)
    ringed = DeviceBackend(DEV, clock=frozen_clock)
    ringed._persistent_interpret = True
    ring = RingBackend(ringed, slots=1, persistent=True)
    try:
        for s in range(2):
            rounds = pack_requests(
                _reqs(s), DEV.batch_size, frozen_clock
            ).rounds
            got = ring.submit_rounds(rounds)()
            want = classic.step_rounds(rounds, add_tally=False)
            assert len(got) == len(want)
            for gh, wh in zip(got, want):
                for col in ("status", "limit", "remaining",
                            "reset_time", "stored", "found"):
                    v = wh[col]
                    np.testing.assert_array_equal(
                        v, gh[col][..., : v.shape[-1]], err_msg=col
                    )
        assert ring.seq_mismatches == 0
        assert ring.debug_vars()["persistent"] is True
    finally:
        ring.close()


def test_persistent_requires_capability_gate():
    """RingBackend refuses persistent=True against a backend with no
    persistent dispatch — the caller must gate on
    persistent_serve_supported(), never assume."""
    from gubernator_tpu.runtime.ring import RingBackend

    class NoPersistent:
        clock = None

        def ring_supported(self):
            return True

    with pytest.raises(ValueError, match="persistent"):
        RingBackend(NoPersistent(), slots=1, persistent=True)


def test_fastpath_persistent_falls_back_to_megaround(frozen_clock):
    """GUBER_SERVE_MODE=persistent on a backend whose kernel cannot
    compile (CPU here) degrades to MEGAROUND — not pipelined — with
    the probe's reason surfaced in /debug/vars; on a mesh backend the
    single-table-only reason surfaces the same way."""
    import asyncio

    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    async def scenario():
        svc = Service(Config(device=DEV), clock=frozen_clock)
        await svc.start()
        fp = FastPath(svc, serve_mode="persistent", ring_slots=2,
                      ring_rounds=2)
        assert fp.serve_mode == "persistent"
        assert fp.effective_serve_mode == "megaround"
        assert fp._ring is not None
        assert fp._ring.rounds == 2 and not fp._ring.persistent
        dv = fp.debug_vars()
        assert dv["persistent"]["supported"] is False
        assert "interpret" in dv["persistent"]["reason"]
        assert dv["ring"]["rounds"] == 2
        await fp.close()
        await svc.close()

        mesh_cfg = DeviceConfig(
            num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
        )
        svc = Service(Config(device=mesh_cfg), clock=frozen_clock)
        await svc.start()
        fp = FastPath(svc, serve_mode="persistent", ring_slots=2,
                      ring_rounds=2)
        assert fp.effective_serve_mode == "megaround"
        assert "single-table" in fp.persistent_status["reason"]
        await fp.close()
        await svc.close()

    asyncio.run(scenario())


def test_megaround_env_knobs(monkeypatch):
    from gubernator_tpu.core.config import (
        ring_linger_us_from_env,
        ring_rounds_from_env,
        setup_daemon_config,
    )

    monkeypatch.setenv("GUBER_SERVE_MODE", "megaround")
    monkeypatch.setenv("GUBER_RING_ROUNDS", "8")
    monkeypatch.setenv("GUBER_RING_MAX_LINGER_US", "500")
    assert ring_rounds_from_env() == 8
    assert ring_linger_us_from_env() == 500.0
    conf = setup_daemon_config()
    assert conf.serve_mode == "megaround"
    assert conf.ring_rounds == 8
    assert conf.ring_max_linger_us == 500.0

    # Startup validation names the env surface (the GUBER_RING_SLOTS
    # discipline): nonsense rejected at parse, not deep in a ctor.
    monkeypatch.setenv("GUBER_RING_ROUNDS", "0")
    with pytest.raises(ValueError, match="GUBER_RING_ROUNDS"):
        setup_daemon_config()
    monkeypatch.setenv("GUBER_RING_ROUNDS", "128")
    with pytest.raises(ValueError, match="GUBER_RING_ROUNDS"):
        setup_daemon_config()
    monkeypatch.setenv("GUBER_RING_ROUNDS", "8")
    monkeypatch.setenv("GUBER_RING_MAX_LINGER_US", "-5")
    with pytest.raises(ValueError, match="GUBER_RING_MAX_LINGER_US"):
        setup_daemon_config()
    monkeypatch.setenv("GUBER_RING_MAX_LINGER_US", "2000000")
    with pytest.raises(ValueError, match="GUBER_RING_MAX_LINGER_US"):
        setup_daemon_config()
    monkeypatch.setenv("GUBER_RING_MAX_LINGER_US", "abc")
    with pytest.raises(ValueError, match="GUBER_RING_MAX_LINGER_US"):
        setup_daemon_config()
    # The knobs COMPOSE: capacity = slots x rounds is bounded too.
    monkeypatch.setenv("GUBER_RING_MAX_LINGER_US", "500")
    monkeypatch.setenv("GUBER_RING_SLOTS", "1024")
    monkeypatch.setenv("GUBER_RING_ROUNDS", "64")
    with pytest.raises(ValueError, match="GUBER_RING_SLOTS x"):
        setup_daemon_config()
