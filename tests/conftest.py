import os

# The persistent compilation cache stays off for the tests and for every
# process they start (the entry points turn it on only where this is unset).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real device count (1 on this container); only launch/dryrun.py forces 512.


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.key(0)
