"""Faults the served path can have, planted in the router underneath the
harness: the check has to come out not correct under each. The tests
plant them at a size a test run holds; ``bench/study.py --fault`` reads
them on the chip at a cell's own size. Not used by a benchmark run."""
from __future__ import annotations

import contextlib

import numpy as np


def _keep_ainv(method):
    """``method`` runs, then A^-1 is put back as it was: a step that
    returns its state unchanged."""
    import jax.numpy as jnp

    def wrapped(self, *a, **kw):
        before = jnp.copy(self.state["ainv"])
        out = method(self, *a, **kw)
        self.state = dict(self.state, ainv=before)
        return out
    return wrapped


def _half_batch(method):
    """The update learns from the first half of the rows only."""
    def wrapped(self, decision, served, rewards, learn_mask=None):
        n = decision["n"]
        mask = np.ones(n, bool) if learn_mask is None else \
            np.asarray(learn_mask, bool).copy()
        mask[n // 2:] = False
        return method(self, decision, served, rewards, learn_mask=mask)
    return wrapped


def _alter_answer(method):
    """Every decided action is moved to the next arm."""
    def wrapped(self, *a, **kw):
        out = method(self, *a, **kw)
        out["action"] = ((out["action"] + 1) % self.num_actions).astype(
            np.int32)
        return out
    return wrapped


def _keep_params(method):
    """The slice runs, then the weights are put back as they were: a
    train step that returns its parameters unchanged."""
    import jax
    import jax.numpy as jnp

    def wrapped(self, *a, **kw):
        before = jax.tree.map(jnp.copy, self.state["params"])
        out = method(self, *a, **kw)
        self.state = dict(self.state, params=before)
        return out
    return wrapped


def _half_train_batch(method):
    """Each replay SGD step draws half its minibatch and takes the mean
    over those rows."""
    def wrapped(self, *a, **kw):
        full = self.batch_size
        self.batch_size = full // 2
        try:
            return method(self, *a, **kw)
        finally:
            self.batch_size = full
    return wrapped


# name: (the DevicePolicyRouter method it breaks, how)
FAULTS = {
    "update_returns_state_unchanged": ("update_wave", _keep_ainv),
    "rebuild_returns_state_unchanged": ("end_slice", _keep_ainv),
    "train_returns_params_unchanged": ("end_slice", _keep_params),
    "half_the_batch_left_out_of_update": ("update_wave", _half_batch),
    "half_the_batch_left_out_of_train": ("end_slice", _half_train_batch),
    "answer_altered_where_produced": ("decide", _alter_answer),
}


@contextlib.contextmanager
def plant(name: str):
    """The router with fault ``name`` in it, for the ``with`` block."""
    from repro.serving.policy_router import DevicePolicyRouter
    method, how = FAULTS[name]
    sound = getattr(DevicePolicyRouter, method)
    setattr(DevicePolicyRouter, method, how(sound))
    try:
        yield
    finally:
        setattr(DevicePolicyRouter, method, sound)
