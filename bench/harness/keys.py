"""The run keys every cell derives from ``--seed``."""
from __future__ import annotations

import jax


def run_key(seed: int, call: int):
    """The ``init`` key of experiment ``call`` under ``--seed``. Seeds up
    to 64 bits are taken whole."""
    base = jax.random.key_data(jax.random.PRNGKey(int(seed) % 2 ** 64))
    return jax.random.fold_in(base, call)
