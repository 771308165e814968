import os
import subprocess
import sys
import textwrap

# Tests run against the single real CPU device; multi-device tests force
# host-platform devices in a child process of their own.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

# hypothesis is an optional dev dependency (requirements-dev.txt).  Tier-1
# must collect and run on a bare jax+pytest environment: register the ci
# profile only when hypothesis is importable; property-based test modules
# guard themselves with pytest.importorskip("hypothesis").
try:
    from hypothesis import settings  # noqa: E402
except ImportError:
    settings = None

if settings is not None:
    settings.register_profile("ci", max_examples=15, deadline=None)
    settings.load_profile("ci")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (subprocess / forced multi-device)")


def run_in_subprocess(argv, *, timeout=600):
    """Run ``argv`` in a fresh interpreter from the repo root.

    The subprocess gets PYTHONPATH=src and is pinned to the CPU backend:
    the forced host-platform devices these tests rely on are CPU devices,
    and a child must never reach for an accelerator its parent may hold.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(argv, env=env, capture_output=True, text=True,
                          timeout=timeout,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def run_script_in_subprocess(script, *, timeout=600):
    """``run_in_subprocess`` for an inline ``python -c`` test script."""
    return run_in_subprocess([sys.executable, "-c", script], timeout=timeout)


def run_forced_device_script(script, *, num_devices=4, marker=None,
                             timeout=600):
    """Run a test script on a forced ``num_devices``-device host platform.

    The shared fixture of every multi-worker engine test: XLA_FLAGS must be
    set before jax imports, so the script runs in a fresh interpreter with
    the forced-device preamble prepended (the main test process keeps its
    single real device).  Asserts success; when ``marker`` is given, also
    asserts the script printed it (the proof it ran to its last line rather
    than silently exiting early).  Returns the completed process for any
    extra stdout checks.
    """
    preamble = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = '
        f'"--xla_force_host_platform_device_count={num_devices}"\n'
    )
    out = run_script_in_subprocess(preamble + textwrap.dedent(script),
                                   timeout=timeout)
    assert out.returncode == 0, (out.stderr[-3000:], out.stdout[-500:])
    if marker is not None:
        assert marker in out.stdout, out.stdout
    return out


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)
