import os
import sys

# Tests must see exactly ONE device (the dry-run subprocess sets its own
# device count); keep any inherited flags out.  CI opts back in to a
# fake multi-device CPU topology via REPRO_HOST_DEVICES=N so the
# shard_map engine path is exercised on plain runners.
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

if os.environ.get("REPRO_HOST_DEVICES"):
    from repro.utils.config import configure
    configure(host_devices=int(os.environ["REPRO_HOST_DEVICES"]))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
