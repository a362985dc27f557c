"""Contract of the driver entry module: importing __graft_entry__
never imports jax, and dryrun_multichip re-execs into a CPU-mesh child
whose environment pins the platform and the host device count.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_overrides: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=600,
    )


def test_import_does_not_import_jax():
    # the contract is that *our* import adds no jax module
    proc = _run(
        "import sys; before = 'jax' in sys.modules; "
        "import __graft_entry__; "
        "assert ('jax' in sys.modules) == before, 'module-level jax import'; "
        "print('ok')",
        {},
    )
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout


def test_respawn_env_is_scrubbed():
    """The child env re-pins JAX_PLATFORMS and XLA_FLAGS, keeps every
    other variable of the parent, and sets no compile-cache directory
    of its own."""
    import __graft_entry__ as g

    poisoned = {
        "JAX_PLATFORMS": "tpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "CELESTIA_FUSED_KERNELS": "0",
    }
    old = {k: os.environ.get(k) for k in
           (*poisoned, "JAX_COMPILATION_CACHE_DIR")}
    os.environ.update(poisoned)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        env = g._scrubbed_env(8)
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
        assert env[g._CHILD_SENTINEL] == "1"
        assert env["CELESTIA_FUSED_KERNELS"] == "0"
        assert "JAX_COMPILATION_CACHE_DIR" not in env
        assert set(env) - set(os.environ) == {g._CHILD_SENTINEL}
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.mark.slow
def test_dryrun_multichip_with_poisoned_parent():
    """The exact driver failure mode: JAX_PLATFORMS=cpu set but the parent
    process's jax state is irrelevant because the child is always fresh."""
    proc = _run(
        "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')",
        {"JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ok" in proc.stdout
