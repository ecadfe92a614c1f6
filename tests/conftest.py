"""Test harness: force an 8-virtual-device CPU JAX platform so sharded paths
are exercised without TPU hardware (SURVEY.md §4 implication (b)/(c))."""

import os
import sys

# the suite runs on the virtual 8-device CPU platform: set the environment
# before jax is imported anywhere (DAS_TPU_TEST_PLATFORM overrides)
os.environ["JAX_PLATFORMS"] = os.environ.get("DAS_TPU_TEST_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def animals_data():
    from das_tpu.models.animals import animals_metta
    from das_tpu.storage.atom_table import load_metta_text

    return load_metta_text(animals_metta())


@pytest.fixture(scope="session")
def animals_db(animals_data):
    from das_tpu.storage.memory_db import MemoryDB

    return MemoryDB(animals_data)


REFERENCE_PATH = "/root/reference"


def reference_available(*parts) -> bool:
    """Whether the reference checkout holds `parts` (default: its `das`
    package).  Checkouts can be partial, so tests ask for what they run."""
    return os.path.exists(os.path.join(REFERENCE_PATH, *(parts or ("das",))))


def reference_path(*parts) -> str:
    """Path of one file of the reference checkout; skips the calling
    test (or fixture) where the checkout does not hold it."""
    if not reference_available(*parts):
        pytest.skip(f"reference checkout has no {'/'.join(parts)}")
    return os.path.join(REFERENCE_PATH, *parts)


@pytest.fixture(scope="session")
def reference_modules():
    """Import the reference pattern matcher + StubDB for differential tests.
    Skips when the reference checkout is absent (CI portability)."""
    if not reference_available():
        pytest.skip("reference checkout not available")
    sys.path.insert(0, REFERENCE_PATH)
    try:
        from das.pattern_matcher import pattern_matcher as ref_pm  # noqa
        from das.database import stub_db as ref_stub  # noqa
    except Exception as exc:  # pragma: no cover
        pytest.skip(f"reference import failed: {exc}")
    return ref_pm, ref_stub


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running scale tests (million-link KBs)"
    )
    config.addinivalue_line(
        "markers",
        "full: heavy blocks (reference-shim subprocesses, fuzz, scale, "
        "multihost) excluded from the quick inner loop",
    )
    config.addinivalue_line(
        "markers",
        "quick: the <5-min inner loop (auto-applied to everything not "
        "marked slow/full); run with `pytest -m quick`",
    )


def pytest_collection_modifyitems(config, items):
    """`pytest -m quick` = everything not slow/full (VERDICT r04 item 9).
    Plain `pytest tests/` still runs the whole suite."""
    for item in items:
        if "slow" not in item.keywords and "full" not in item.keywords:
            item.add_marker(pytest.mark.quick)
