"""Test harness config: run everything on a virtual 8-device CPU mesh
(SURVEY.md §4 implications: multi-host logic tested the way the reference ran
master+slave on loopback — here via xla_force_host_platform_device_count).

Tests run on the CPU because the meshes they build are CPU meshes: the
platform is pinned here so that no test reaches for an accelerator, and
XLA_FLAGS (read at backend init) gives the eight virtual devices.  The
chip is reached only by ``chip_smoke.py`` through the chip tool.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# The persistent compilation cache is on by default in the product
# (runtime/step_cache.py) and lives inside the checkout; the suite's
# thousands of tiny CPU programs — here and in the CLI subprocesses the
# tests start — must not be written there.  The tests of the cache
# itself switch it back on around their compiles.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests")
    config.addinivalue_line(
        "markers", "faults: fault-injection / robustness tests (tier-1; "
        "select alone with -m faults)")
    config.addinivalue_line(
        "markers", "artifact: compiled-artifact export/runner tests "
        "(tier-1; select alone with -m artifact)")
    config.addinivalue_line(
        "markers", "paged: paged KV cache / shared-prefix reuse tests "
        "(tier-1; select alone with -m paged)")
    config.addinivalue_line(
        "markers", "analysis: static-analyzer (veles-tpu-lint) tests "
        "incl. the zero-findings gate (tier-1; select alone with "
        "-m analysis)")
    config.addinivalue_line(
        "markers", "spec: speculative-decoding / verify-program tests "
        "(tier-1; select alone with -m spec)")
    config.addinivalue_line(
        "markers", "overload: overload-survival tests — chunked "
        "prefill, priority preemption, admission control (tier-1; "
        "select alone with -m overload)")
    config.addinivalue_line(
        "markers", "fleet: multi-replica fleet-router tests — "
        "affinity dispatch, coordinated swap, rolling drain, "
        "ejection/resubmission (tier-1; select alone with -m fleet)")
    config.addinivalue_line(
        "markers", "megastep: fused multi-micro-step decode tests — "
        "bitwise identity, in-program retirement, artifact sealing "
        "(tier-1; select alone with -m megastep)")
    config.addinivalue_line(
        "markers", "disagg: disaggregated prefill/decode tests — "
        "KV-page wire format, fleet transfer, capacity roles, drain "
        "pre-warm (tier-1; select alone with -m disagg)")
    config.addinivalue_line(
        "markers", "jobs: batch job manager / trough-filler lane tests "
        "— durable store, REST job API, batch-class preemption "
        "(tier-1; select alone with -m jobs)")
    config.addinivalue_line(
        "markers", "streaming: streaming serving / crash-safe resume "
        "tests — per-token frames, stop sequences, mid-stream "
        "failover (tier-1; select alone with -m streaming)")
    config.addinivalue_line(
        "markers", "experiments: experiment-manager tests — durable "
        "store resume, search policies, generation replay, batch-lane "
        "scoring, promotion gate (tier-1; select alone with "
        "-m experiments)")


# -- tier-1 wall budget -------------------------------------------------------
# The tier-1 suite (-m 'not slow') is the per-PR gate; every PR adds
# tests, and a gate that quietly drifts past the CI timeout fails in
# the worst possible way (killed mid-run, no culprit named).  Budget
# the wall here instead: when a tier-1 run exceeds the budget, fail
# the SESSION loudly with the slowest offenders listed, so the PR that
# broke the budget is the PR that pays for it.  The default is
# calibrated to the measured full-suite wall on the dev box (~910s at
# 663 tests) plus ~20% headroom for machine noise — re-measure and
# re-calibrate (or slow-mark offenders, the PR-14 fire drill) when a
# trip names this budget rather than a runaway test.

_TIER1_WALL_BUDGET_S = float(os.environ.get(
    "VT_TIER1_WALL_BUDGET_S", "1100"))
_tier1_state = {"t0": None, "durations": []}


def _is_tier1_run(config) -> bool:
    return "not slow" in (config.getoption("-m", default="") or "")


def pytest_sessionstart(session):
    if _is_tier1_run(session.config):
        import time as _time
        _tier1_state["t0"] = _time.monotonic()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if _tier1_state["t0"] is None:
        yield
        return
    import time as _time
    t0 = _time.monotonic()
    yield
    _tier1_state["durations"].append(
        (_time.monotonic() - t0, item.nodeid))


def pytest_sessionfinish(session, exitstatus):
    if _tier1_state["t0"] is None:
        return
    import time as _time
    wall = _time.monotonic() - _tier1_state["t0"]
    if wall <= _TIER1_WALL_BUDGET_S:
        return
    slowest = sorted(_tier1_state["durations"], reverse=True)[:10]
    lines = [f"  {d:8.1f}s  {nodeid}" for d, nodeid in slowest]
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    msg = (f"tier-1 wall budget exceeded: {wall:.0f}s > "
           f"{_TIER1_WALL_BUDGET_S:.0f}s "
           "(VT_TIER1_WALL_BUDGET_S); slowest tests:\n"
           + "\n".join(lines))
    if tr is not None:
        tr.write_sep("=", "tier-1 wall budget", red=True)
        tr.write_line(msg)
    session.exitstatus = 1


@pytest.fixture(autouse=True)
def _reset_prng():
    from veles_tpu import prng
    prng.streams.reset()
    yield
    prng.streams.reset()


@pytest.fixture(autouse=True)
def _no_autotune():
    """Autotune off under test: measured winners differ per machine (and
    the two LRN formulations round differently), which would make golden
    numerics flaky; tests that exercise autotune flip it back on."""
    from veles_tpu.config import root
    prev = root.common.autotune
    root.common.autotune = False
    yield
    root.common.autotune = prev


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
