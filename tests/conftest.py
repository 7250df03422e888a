"""Test configuration: force an 8-device virtual CPU mesh.

The reference tests distributed code in Spark local[*] mode
(SparkTestUtils.scala:56-75); the TPU-native analog is JAX's host-platform
device-count override, which gives real multi-device sharding/collective
semantics on CPU without TPU hardware (SURVEY.md §4).

Must run before jax initializes, hence module-level os.environ writes in
conftest (pytest imports conftest before test modules import jax).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep CPU compiles single-threaded-ish and quiet for CI stability.
os.environ.setdefault("JAX_TRACEBACK_FILTERING", "off")
# Cache EVERY program: the suite's cost is hundreds of 0.1-0.5s compiles
# (profiled: 81 compiles x 0.138s in ONE game test), all below the 1s
# default write threshold — without this the "warm" suite recompiles
# nearly everything, and the CLI subprocess tests can never hit the cache
# their parent process populated. Env vars (read by jax at import) so
# subprocesses inherit them through dict(os.environ).
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Persistent XLA compile cache: the suite is compile-dominated (dozens of
# while_loop optimizer programs). The program's own rule places it (a
# pre-set JAX_COMPILATION_CACHE_DIR wins, else the fixed in-checkout
# directory); exporting the result lets subprocess tests that do not go
# through `python -m photon_ml_tpu.cli` inherit the SAME cache.
from photon_ml_tpu.utils import enable_compile_cache

os.environ["JAX_COMPILATION_CACHE_DIR"] = enable_compile_cache()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multichip: needs an 8-device mesh; when this process has fewer "
        "devices the test transparently re-runs itself in a subprocess "
        "under XLA_FLAGS=--xla_force_host_platform_device_count=8 "
        "JAX_PLATFORMS=cpu (the multichip fixture)",
    )


@pytest.fixture
def multichip(request):
    """Tier-1-runnable multichip CI: guarantee the test sees >= 8 devices.

    In the normal suite this conftest already forced an 8-device virtual
    CPU platform, so the fixture is a pass-through. When the suite runs in
    an environment that latched a different platform (a 1-chip TPU host,
    a site customization importing jax early), the test re-execs ITSELF
    via pytest in a subprocess with the forced flags — so sharded-vs-
    single-device parity always runs somewhere, never silently skips.
    """
    if jax.device_count() >= 8:
        return jax.devices()[:8]
    if os.environ.get("PHOTON_MULTICHIP_SUBPROCESS") == "1":
        pytest.fail(
            "forced 8-device CPU provisioning failed: subprocess still "
            f"sees {jax.device_count()} devices on "
            f"{jax.devices()[0].platform}"
        )
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PHOTON_MULTICHIP_SUBPROCESS"] = "1"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-q",
            "-p", "no:cacheprovider", request.node.nodeid,
        ],
        env=env,
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=900,
    )
    if proc.returncode != 0:
        pytest.fail(
            "multichip subprocess rerun failed "
            f"(rc={proc.returncode}):\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-1000:]}"
        )
    pytest.skip(
        "passed in a forced 8-device CPU subprocess (this process has "
        f"only {jax.device_count()} devices)"
    )


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Telemetry is process-global (spans, counters, sinks, env-configured
    atexit flushes); without a guard, test ORDER decides whether one
    test's sink or stats provider leaks into the next. Reset after every
    test — telemetry.reset() restores full import-time defaults."""
    yield
    from photon_ml_tpu import telemetry

    telemetry.reset()
