"""Faults planted under the timed path, to show that the comparison
fails them.  Each is a context manager that patches the program for as
long as it is entered; ``bench/control.py`` and the CPU tests run a
cell under each and expect ``correct`` to come out false.  Faults of
one architecture's own mechanism live in its module's ``FAULTS``
(``bench/archs``); :func:`of` gives a cell's faults, both together.
"""

from __future__ import annotations

from contextlib import contextmanager

import numpy as np


@contextmanager
def _patched(obj, name: str, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def unchanged_state():
    """Every step hands back the weights it was given."""
    import repro.optim.adamw as adamw

    def make(orig):
        def frozen(params, grads, state, cfg):
            _, state, metrics = orig(params, grads, state, cfg)
            return params, state, metrics
        return frozen

    return _patched(adamw, "sharded_apply_updates", make)


def _feeds_fault(change):
    from repro.api.session import Session

    def make(orig):
        def train_step(self, feeds=None, **kw):
            return orig(self, change({k: np.array(v) for k, v in
                                      feeds.items()}), **kw)
        return train_step

    return _patched(Session, "train_step", make)


def half_batch():
    """Each step leaves out the second half of its batch: the first
    half stands in for it, so the mean is taken over the rest.  A batch
    of one row loses the second half of its positions instead."""
    def change(feeds):
        axis = 0 if len(feeds["ids"]) > 1 else 1
        return {k: np.concatenate([np.split(v, 2, axis)[0]] * 2, axis)
                for k, v in feeds.items()}
    return _feeds_fault(change)


def altered_token():
    """One label of every step is changed where the batch is made."""
    def change(feeds):
        feeds["labels"][0, 0] ^= 1
        return feeds
    return _feeds_fault(change)


def bfloat16_weights():
    """The weights are rounded to bfloat16 as they are loaded: a stand-in
    for a lower precision where the backend ignores the matmul
    precision (XLA:CPU)."""
    import jax.numpy as jnp
    from repro.api.session import Session

    def make(orig):
        def load(self, values):
            return orig(self, {n: np.asarray(jnp.asarray(v, jnp.bfloat16)
                                              .astype(jnp.float32))
                               for n, v in values.items()})
        return load

    return _patched(Session, "load", make)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_token": altered_token}


def of(arch) -> dict:
    """The faults a cell of architecture ``arch`` can have: ``FAULTS``
    and the module's own ``FAULTS``.  A name in both is an error."""
    own = getattr(arch, "FAULTS", {})
    both = sorted(set(FAULTS) & set(own))
    if both:
        raise ValueError(f"faults {both} are defined both in "
                         f"bench/faults.py and in {arch.__file__}")
    return {**FAULTS, **own}
