"""Shared by every test tree of the repository."""
import pytest


@pytest.fixture(autouse=True)
def _fresh_simulator_loops():
    """Each test starts with no kept simulator loop.  The loops' key
    reads a call's statics, not the code it traces, so a loop kept by an
    earlier test would hide a function that this test replaces (a
    planted fault, a counted kernel)."""
    from repro.core.program import clear_scan_cache
    clear_scan_cache()
    yield
