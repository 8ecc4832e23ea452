import os

# Sharding tests run on a virtual 8-device CPU mesh; the engine host plane
# doesn't need the TPU, and tests must not depend on one being attached.
# Set before anything imports jax; child processes inherit both.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--regen-plans",
        action="store_true",
        default=False,
        help="rewrite tests/plans/*.txt golden execution plans from the "
        "current optimizer instead of comparing against them",
    )


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: deterministic chaos/fault-injection
    # tests stay in tier-1 (marker `chaos`), long randomized drills are
    # additionally marked `slow` and excluded
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection / crash-recovery test"
    )
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the tier-1 run"
    )


@pytest.fixture(autouse=True)
def fresh_graph():
    """Reset the global graph between tests (reference
    ``python/pathway/conftest.py`` resets ParseGraph per test)."""
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()
