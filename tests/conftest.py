import os
import sys

# Tests must see exactly ONE device (the dry-run subprocess sets its own
# device count); keep any inherited flags out.
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
