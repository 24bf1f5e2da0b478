import os
import sys

# Tests run on the single host CPU device (the dry-run, and only the
# dry-run, forces 512 devices — see src/repro/launch/dryrun.py).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

# NOTE: x64 is NOT enabled globally (it would change default dtypes across
# the whole suite); the fp64 merge-error test (paper Table 4) uses the
# jax.enable_x64 scoped context instead.


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
