"""On-chip test harness: unlike tests/ (which pins the CPU backend for the
8-virtual-device mesh), this suite compiles and runs on the attached TPU,
and the session FAILS — it does not skip — where there is none. Run it on
the chip machine, in one process (the chip has one owner):

    python -m pytest tests_chip -q
"""

import jax
import pytest


def pytest_sessionstart(session):
    platform = jax.devices()[0].platform
    if platform != "tpu":
        pytest.exit(
            f"tests_chip needs a TPU; JAX found platform {platform!r}",
            returncode=1,
        )
