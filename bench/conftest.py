"""The whole-run fault tests (``bench/tests/test_bench_faults.py``) drive
every cell in a subprocess on the CPU.  A cell that asks for four chips needs
four devices there, so for that module the subprocess sees four host CPU
devices; the test process itself keeps the devices it has."""
import os

import pytest

FOUR_DEVICES = "--xla_force_host_platform_device_count=4"


@pytest.fixture(scope="module", autouse=True)
def _four_host_devices_for_whole_runs(request):
    if request.path.name != "test_bench_faults.py":
        yield
        return
    old = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = f"{old} {FOUR_DEVICES}" if old else FOUR_DEVICES
    try:
        yield
    finally:
        if old is None:
            del os.environ["XLA_FLAGS"]
        else:
            os.environ["XLA_FLAGS"] = old
