import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# Skip collecting test modules whose hard dependencies are not present in
# this build, instead of aborting the whole run at collection time.  The
# table is DATA so the skip set is auditable: `SKIP_REASONS` records WHY
# each module was dropped, `pytest_report_header` prints it at the top of
# every run, and tests/test_dep_skip_guard.py fails the suite if an entry
# here names a dependency that actually exists (a stale skip silently
# hiding real tests).
_DEP_SKIPS = {
    "hypothesis": ["test_legalizer.py", "test_midend.py",
                   "test_property_system.py"],
}


def _have(module: str) -> bool:
    try:
        return importlib.util.find_spec(module) is not None
    except ModuleNotFoundError:   # parent package itself not importable
        return False


collect_ignore = []
SKIP_REASONS = {}   # test module -> missing import name
for _dep, _modules in _DEP_SKIPS.items():
    if not _have(_dep):
        collect_ignore += _modules
        for _m in _modules:
            SKIP_REASONS[_m] = _dep


def pytest_report_header(config):
    if not SKIP_REASONS:
        return ["dep-skips: none (all optional deps present)"]
    return ["dep-skips: " + ", ".join(
        f"{m} (missing {dep!r})" for m, dep in sorted(SKIP_REASONS.items()))]


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600
                     ) -> str:
    """Run `code` in a subprocess with N fake host devices.

    Multi-device tests must not pollute this process's jax device state
    (smoke tests see 1 device), so they execute in a child interpreter.
    Raises on failure with combined output.
    """
    env = dict(os.environ)
    # the child runs on host devices, never on an accelerator this
    # machine may have (one process per chip: the child would hang)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", ""))
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode}):\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    return proc.stdout


@pytest.fixture
def subproc():
    return run_with_devices
