"""Test config: run JAX on a virtual 8-device CPU mesh so parallelism tests
exercise real shardings without TPU hardware (the driver separately dry-runs
the multi-chip path; chip_smoke.py and perf/run.py use the real chip).

JAX_PLATFORMS and XLA_FLAGS are set here, before the first jax import —
JAX reads both at backend start-up."""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# --thread-excepthook-strict: background-thread exceptions fail the test
# that was running when they fired, instead of scrolling past as console
# noise. pytest's threadexception plugin already hooks
# threading.excepthook per test and downgrades a dead thread to
# PytestUnhandledThreadExceptionWarning; this flag escalates that warning
# to an error. The serving runtime leans on daemon threads (batcher loop,
# ipc drain, persistence) whose deaths are otherwise silent — CI runs the
# tier-1 suite with this flag (plus `python -X dev`) so a swallowed
# background traceback goes RED. Opt a test out with
# @pytest.mark.allow_thread_exceptions when the death is the point.
# ---------------------------------------------------------------------------


def pytest_addoption(parser):
    parser.addoption(
        "--thread-excepthook-strict", action="store_true", default=False,
        help="fail a test when a background thread dies with an unhandled "
             "exception during it (escalates pytest's unhandled-thread-"
             "exception warning to an error)")


def pytest_collection_modifyitems(config, items):
    if not config.getoption("--thread-excepthook-strict"):
        return
    strict = pytest.mark.filterwarnings(
        "error::pytest.PytestUnhandledThreadExceptionWarning")
    lenient = pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    for item in items:
        # marker-applied filters win over ini ones; applying per item keeps
        # the opt-out marker working
        item.add_marker(lenient if item.get_closest_marker(
            "allow_thread_exceptions") else strict)


# ---------------------------------------------------------------------------
# leakcheck canary (ISSUE 19): for tests marked ``leakcheck``, every
# ContinuousBatcher constructed DURING the test is tracked, and any that
# finished the test cleanly closed must show zero resource residue —
# pages held by slots, elevated trie pins, adapter pins, staged remote
# jobs, undelivered handoffs (testing/faults.py LeakSweep.residue). A
# crashed batcher is exempt (its allocator dies with it; the fleet layer
# owns that recovery), and a still-open one is a shared module-scoped
# service whose slots may legitimately be warm. This is the standing
# version of the leak sweep: every disagg/radix/adapter/chaos test run
# doubles as a leak regression.
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _leak_canary(request):
    if request.node.get_closest_marker("leakcheck") is None:
        yield
        return
    import weakref

    from seldon_core_tpu.runtime import batcher as _bmod

    created = []
    real_init = _bmod.ContinuousBatcher.__init__

    def tracking_init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        created.append(weakref.ref(self))

    _bmod.ContinuousBatcher.__init__ = tracking_init
    try:
        yield
    finally:
        _bmod.ContinuousBatcher.__init__ = real_init
        from seldon_core_tpu.testing.faults import LeakSweep

        for ref in created:
            b = ref()
            if b is None or b.crashed is not None or not b._closed:
                continue
            residue = {k: v for k, v in LeakSweep(b).residue().items()
                       if v != 0}
            assert not residue, (
                f"leakcheck: closed batcher left residue {residue} — an "
                f"error/shed path dropped a release (see docs/"
                f"static-analysis.md, leaklint)")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


def wait_http_ready(port, proc, path="/ready", deadline_s=60.0):
    """Shared subprocess-server readiness wait: polls the endpoint and
    fast-fails if the process died (used by the rollout + cluster e2e
    suites; one copy so the dead-process fix can't drift)."""
    import time
    import urllib.request

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(f"server exited rc={proc.returncode} before ready")
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=1) as r:
                if r.status == 200:
                    return
        except Exception:
            time.sleep(0.2)
    raise TimeoutError(f"server never became ready on {path}")
