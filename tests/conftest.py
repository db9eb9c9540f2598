"""Shared test harness: the CPU platform and the ``--runslow`` tier.

The suite runs on the CPU: the Pallas kernels run in interpret mode, which
each test asks for (``interpret=True``).  ``JAX_PLATFORMS`` defaults to
``cpu`` so jax does not probe for a TPU at first use; with libtpu installed
and no chip attached that probe stalls for minutes.  What only the TPU
compiler checks (VMEM limits, lane-aligned DMA slices, scalar-memory loads)
is covered by ``tests/test_tpu_compile.py``, which compiles the kernels for
a described v5e without a chip.  On a chip, ``python3 chip_smoke.py`` at the
repo root serves the model end to end.

The ``slow`` marker (registered here and in pyproject.toml) carves the
suite into the fast tier-1 run (default, minutes) and the long
model/runtime/subprocess tests, opted back in with ``--runslow``.
"""

from __future__ import annotations

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# --------------------------------------------------------------------------- #
# test tiers
# --------------------------------------------------------------------------- #


def pytest_addoption(parser):
    parser.addoption(
        "--runslow",
        action="store_true",
        default=False,
        help="also run tests marked slow (full tier-2 sweep)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, skipped unless --runslow is given"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow test: pass --runslow to include")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
