import os

# Tests must see exactly 1 CPU device (dry-run sets 512 in its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
