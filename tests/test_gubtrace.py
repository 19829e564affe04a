"""gubtrace self-tests: every checker catches its seeded-violation
fixture, the real kernel registry scans clean (golden snapshots intact,
recompile audit at zero unexpected misses), and the end-to-end donation
contract holds on CPU (donated buffers actually die).

The fixtures live in tests/gubtrace_fixtures/ — violating kernels are
registered through the `specs=` override, never the real registry.
"""
from pathlib import Path

import numpy as np
import pytest

from tools.gubtrace import ALL_CHECKERS, GOLDEN_DIR, run
from tools.gubtrace.completeness import RegistryCompletenessChecker
from tools.gubtrace.core import RunContext

FIXTURES = Path(__file__).parent / "gubtrace_fixtures"
REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def fixture_findings():
    from tests.gubtrace_fixtures.kernels import FIXTURE_SPECS

    # Every checker except registry-completeness (which scans the real
    # tree); each fixture spec enables only the checker it seeds.
    return run(
        select=[c for c in ALL_CHECKERS if c != "registry"],
        specs=FIXTURE_SPECS,
        golden_dir=FIXTURES / "golden",
        root=REPO,
    )


def _of(findings, kernel):
    return [f for f in findings if f.kernel == kernel]


# -- each checker catches its seeded violation ---------------------------
def test_dtype_catches_narrowing(fixture_findings):
    fs = _of(fixture_findings, "viol_dtype_narrow")
    assert any(
        f.checker == "dtype-taint" and "to_i32" in f.message
        and f.severity == "error" for f in fs
    ), fixture_findings


def test_dtype_catches_float_demotion(fixture_findings):
    fs = _of(fixture_findings, "viol_dtype_float")
    assert any(
        f.checker == "dtype-taint" and "to_f32" in f.message for f in fs
    ), fixture_findings


def test_hostescape_catches_callback(fixture_findings):
    fs = _of(fixture_findings, "viol_hostescape")
    assert any(
        f.checker == "host-escape" and "callback" in f.message
        for f in fs
    ), fixture_findings


def test_donation_catches_dropped_donation(fixture_findings):
    fs = _of(fixture_findings, "viol_donation")
    assert any(
        f.checker == "donation" and "dropped" in f.message for f in fs
    ), fixture_findings


def test_budget_catches_extra_gather(fixture_findings):
    fs = _of(fixture_findings, "viol_budget")
    assert any(
        f.checker == "primitive-budget"
        and "gather: golden 1 -> observed 2" in f.message for f in fs
    ), fixture_findings


def test_recompile_catches_weak_type_miss(fixture_findings):
    fs = _of(fixture_findings, "viol_recompile")
    assert any(
        f.checker == "recompile" and "observed 2" in f.message
        and "declared 1" in f.message for f in fs
    ), fixture_findings


def test_spec_suppression_silences_checker(fixture_findings):
    assert _of(fixture_findings, "viol_dtype_suppressed") == []


def test_registry_completeness_catches_unregistered():
    ch = RegistryCompletenessChecker(
        registered=(), watched=("viol_unregistered.py",)
    )
    ctx = RunContext(root=FIXTURES, golden_dir=FIXTURES / "golden")
    fs = list(ch.finalize(ctx))
    assert any(
        f.kernel == "sneaky_kernel" and "not in the gubtrace registry"
        in f.message for f in fs
    ), fs
    # The pragma'd assignment is exempt.
    assert not any(f.kernel == "exempt_kernel" for f in fs), fs


# -- the real registry scans clean ---------------------------------------
def test_registry_scans_clean():
    """The full verifier over the live kernel registry: every checker,
    every kernel, golden snapshots intact, recompile audit at zero
    unexpected misses.  This is the same run CI's gubtrace job does."""
    from tools.gubtrace.registry import specs

    ctx_out = []
    findings = run(root=REPO, ctx_out=ctx_out)
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], "\n".join(f.render() for f in errors)
    names = [s.name for s in specs()]
    # Every registered kernel actually traced (none skipped)...
    assert sorted(ctx_out[0].jaxprs) == sorted(names)
    assert ctx_out[0].skipped == []
    # ...and carries a committed golden snapshot.
    for n in names:
        assert (GOLDEN_DIR / f"{n}.json").is_file(), n


def test_cli_list_names_every_kernel():
    import subprocess
    import sys

    from tools.gubtrace.registry import registered_names

    proc = subprocess.run(
        [sys.executable, "-m", "tools.gubtrace", "--list"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for name in registered_names():
        assert name in proc.stdout


# -- end-to-end donation regression (CPU) --------------------------------
# The static donation checker proves the aliasing is in the lowering;
# these prove the runtime effect: after the step, the donated input
# buffers are actually gone (a future jax/XLA regression that silently
# stops honoring donation fails here, not in an HBM graph).
def test_apply_batch_consumes_donated_table():
    import jax

    from gubernator_tpu.ops.state import init_table
    from gubernator_tpu.ops.step import apply_batch
    from tools.gubtrace.registry import _device_batch

    table = init_table(4096)
    leaves = jax.tree_util.tree_leaves(table)  # the 20 physical columns
    new_table, resp = apply_batch(table, _device_batch(64), np.int64(0))
    jax.block_until_ready(new_table)
    deleted = [leaf.is_deleted() for leaf in leaves]
    assert all(deleted), (
        f"{sum(not d for d in deleted)} donated table buffers survived "
        "apply_batch — donation regressed end-to-end"
    )


def test_cms_step_consumes_donated_state():
    import jax

    from gubernator_tpu.ops.sketch import cms_step, init_sketch

    state = init_sketch(4, 1024)
    leaves = list(state)
    B = 128
    new_state, over, est = cms_step(
        state,
        np.zeros(B, np.int64), np.zeros(B, np.int32),
        np.zeros(B, np.int32), np.int64(0),
    )
    jax.block_until_ready(new_state)
    deleted = [leaf.is_deleted() for leaf in leaves]
    assert all(deleted), (
        f"{sum(not d for d in deleted)} donated sketch buffers "
        "survived cms_step — donation regressed end-to-end"
    )


# -- runtime recompile report: live jit-cache counts of this process ------
def test_runtime_cache_report_sees_module_kernels():
    from tools.gubtrace.recompile import runtime_cache_report

    # The donation tests above compiled apply_batch and cms_step in
    # this process; the report must see non-empty caches for them.
    report = runtime_cache_report()
    assert report["gubernator_tpu.ops.step.apply_batch"] >= 1
    assert report["gubernator_tpu.ops.sketch.cms_step"] >= 1
    # And cover every module-level jit the registry watches.
    assert "gubernator_tpu.ops.step.probe_batch" in report
