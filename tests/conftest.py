"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware. This must happen before jax is imported.
"""

import os

# Tests run on the virtual 8-device CPU mesh, never on an attached chip
# (tests/test_chip_compile.py compiles for a described one instead).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache (repo-local .jax_cache, shared with the
# driver dryrun): repeat suite runs load compiled programs from disk
# instead of re-lowering every jit — the dominant cost of the device-path
# tests on the CPU mesh. Keyed by platform/flags/program, so it can only
# cause a recompile, never a wrong result.
from celestia_tpu.ops import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--all",
        action="store_true",
        default=False,
        help="run the full suite including slow multi-process/devnet tests",
    )
    parser.addoption(
        "--san",
        action="store_true",
        default=False,
        help="run under the celestia-san runtime sanitizer (specs/analysis.md "
             "T-rules): lock factories instrumented for the whole session, "
             "any new T-finding fails the run",
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running tests (full 128x128 squares)")
    config.addinivalue_line("markers", "tpu: tests requiring a real TPU device")


@pytest.fixture(scope="session", autouse=True)
def _san_session(request):
    """`pytest --san`: one sanitizer Session spanning the whole run.

    Coverage rules (T005) are skipped — a test subset legitimately
    exercises only part of the declared order; `make san` owns the
    coverage gate. A new T001/T002/T003/T004 finding fails the run via
    a teardown error (the reliable way to force a nonzero exit from a
    session fixture)."""
    if not request.config.getoption("--san"):
        yield
        return
    import pathlib

    from celestia_tpu.tools.sanitizer import (
        Session, activate, deactivate, finalize,
    )

    session = Session()
    activate(session)
    try:
        yield
    finally:
        deactivate(session)
    root = pathlib.Path(__file__).resolve().parents[1]
    report = finalize(session, root, coverage=False)
    if report.new_findings:
        rendered = "\n".join(f.render() for f in report.new_findings)
        raise RuntimeError(
            f"celestia-san: {len(report.new_findings)} new runtime "
            f"finding(s) during the sanitized test session:\n{rendered}")


def pytest_collection_modifyitems(config, items):
    """Tiered execution (the reference's test/test-short split,
    Makefile:124-131): slow suites — multi-process devnet, gRPC,
    multihost, RPC race storms — run only with `--all` (or an explicit
    `-m slow`), keeping the default developer loop fast."""
    if config.getoption("--all") or config.getoption("-m"):
        return
    skip_slow = pytest.mark.skip(
        reason="slow tier: run with --all (make test-all)"
    )
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
