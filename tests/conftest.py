import os
import sys

# Tests run against the real single CPU device (the 512-device flag is
# set ONLY inside launch/dryrun.py, never globally).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Graceful degradation: if the real hypothesis package is missing, fall
# back to the deterministic shim in tests/_compat so the whole suite
# still collects and the property tests run as light fuzz tests.
try:
    import hypothesis  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "_compat"))


import pytest  # noqa: E402

from _blockstore import build_block_store  # noqa: E402


@pytest.fixture(scope="session")
def block_store(tmp_path_factory):
    """A store of 320 short tracks in 4-5 shards of dozens of blocks."""
    return build_block_store(str(tmp_path_factory.mktemp("block_store")))
