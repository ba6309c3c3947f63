"""Test harness: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's TestGeoMesaDataStore strategy (SURVEY.md section 4):
the full stack runs against an in-memory backend with zero infra — here,
JAX CPU with a forced 8-device host platform so multi-device sharding
tests run without a TPU pod.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# (the chip is exercised by chip_smoke.py, not by this suite; the TPU's
# compiler is exercised without a chip by tests/test_chip_compile.py)


def pytest_configure(config):
    # tier-1 runs with `-m 'not slow'` (ROADMAP): long randomized suites
    # (crash matrices, fuzzers) carry the slow marker
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run"
    )
