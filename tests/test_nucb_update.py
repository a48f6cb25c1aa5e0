"""Property suite for the fused rank-k Woodbury update kernel
(`kernels.nucb_update`, DESIGN.md §15.1) — the third leg of Algorithm
1's hot path — plus the `REPRO_KERNEL_BACKEND` backend-override gate.

Parity pins (ISSUE acceptance):

* kernel (interpret mode on CPU) vs ``sherman_morrison_batch``:
  <= 2e-4 end-to-end;
* jnp backend vs ``woodbury_update``: BIT-level in f32 (the ref
  delegates verbatim, and dispatch must actually take that path);
* across block sizes, k=0, k=1, k>d, bf16 features, and all-dead
  (w=0) rows.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep — fall back to the local stub
    from _hypothesis_stub import given, settings, st

from repro.core import neuralucb as NU
from repro.kernels import backend as KB
from repro.kernels.nucb_update import nucb_update, nucb_update_ref

INTERPRET = not KB.on_tpu()
SM_ATOL = 2e-4     # kernel vs the sequential Sherman-Morrison oracle


def _case(seed, n, d, scale=0.3, warm=True):
    """A non-trivial SPD A^-1 (a few updates applied) plus fresh rows."""
    rng = np.random.default_rng(seed)
    ainv = NU.init_ainv(d, 1.0)
    if warm and n:
        ainv = NU.woodbury_update(
            ainv, jnp.asarray(rng.normal(size=(max(1, n // 2), d))
                              .astype(np.float32) * scale))
    gs = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) * scale)
    return ainv, gs


@settings(deadline=None, max_examples=25)
@given(n=st.sampled_from([0, 1, 5, 64, 200, 300]),
       d=st.sampled_from([3, 9, 64, 130]),
       block_k=st.sampled_from([32, 128, 256]))
def test_nucb_update_matches_sherman_morrison(n, d, block_k):
    """k=0 / k=1 / k>d / multi-block all land within SM_ATOL of the
    n-sequential-rank-1 oracle (the paper's exact recurrence)."""
    ainv, gs = _case(0, n, d)
    ref = NU.sherman_morrison_batch(ainv, gs)
    got = nucb_update(ainv, gs, block_k=block_k, interpret=INTERPRET)
    assert got.shape == (d, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=SM_ATOL, rtol=0)


@settings(deadline=None, max_examples=10)
@given(n=st.sampled_from([1, 37, 260]), d=st.sampled_from([9, 130]))
def test_nucb_update_jnp_backend_bit_level(n, d):
    """The jnp backend IS ``woodbury_update`` — bit-identical in f32."""
    ainv, gs = _case(1, n, d)
    want = NU.woodbury_update(ainv, gs)
    got = nucb_update(ainv, gs) if not KB.on_tpu() else nucb_update_ref(
        ainv, gs)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_nucb_update_all_dead_rows_is_identity():
    """w=0 rows are exact no-ops: an all-masked batch leaves A^-1
    BIT-unchanged through the kernel (zero rows -> identity S)."""
    ainv, gs = _case(2, 64, 9)
    dead = gs * jnp.zeros((64, 1))
    got = nucb_update(ainv, dead, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ainv),
                               atol=1e-6, rtol=0)
    # and mixed: dead rows contribute nothing next to live ones
    mask = jnp.asarray((np.arange(64) % 3 == 0).astype(np.float32))
    got_mixed = nucb_update(ainv, gs * mask[:, None], interpret=INTERPRET)
    ref_mixed = NU.sherman_morrison_batch(ainv, gs * mask[:, None])
    np.testing.assert_allclose(np.asarray(got_mixed), np.asarray(ref_mixed),
                               atol=SM_ATOL, rtol=0)


def test_nucb_update_bf16_features():
    """bf16 feature rows are accepted and cast at the kernel boundary;
    A^-1 stays f32 statistics state on every path."""
    ainv, gs = _case(3, 100, 30)
    gs16 = gs.astype(jnp.bfloat16)
    got = nucb_update(ainv, gs16, interpret=INTERPRET)
    assert got.dtype == jnp.float32
    ref = NU.sherman_morrison_batch(ainv, gs16.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=SM_ATOL, rtol=0)


def test_woodbury_update_blocked_matches_single_block():
    """The fori_loop multi-block path (padded tail included) matches the
    one-shot Woodbury solve and the sequential oracle."""
    ainv, gs = _case(4, 200, 9)
    one = NU._woodbury_block(ainv, gs)
    multi = NU.woodbury_update(ainv, gs, block_size=64)   # 200 = 3*64 + 8
    seq = NU.sherman_morrison_batch(ainv, gs)
    np.testing.assert_allclose(np.asarray(multi), np.asarray(one),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(multi), np.asarray(seq),
                               atol=SM_ATOL, rtol=0)
    # k=0 is the identity
    assert np.array_equal(np.asarray(NU.woodbury_update(ainv, gs[:0])),
                          np.asarray(ainv))


# ------------------------------------------------ backend env override --
def test_backend_env_override(monkeypatch):
    """REPRO_KERNEL_BACKEND forces the interpret=None auto-detection;
    explicit interpret=True/False still wins; unknown values raise."""
    for val, want in (("jnp", KB.REF), ("pallas", KB.PALLAS),
                      ("interpret", KB.INTERPRET), ("  PALLAS ", KB.PALLAS)):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", val)
        assert KB.resolve_backend(None) == want, val
        assert KB.resolve_backend(True) == KB.INTERPRET
        assert KB.resolve_backend(False) == KB.PALLAS
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "cuda")
    with pytest.raises(ValueError, match="REPRO_KERNEL_BACKEND"):
        KB.resolve_backend(None)
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    assert KB.resolve_backend(None) == (KB.PALLAS if KB.on_tpu() else KB.REF)


def test_backend_env_override_reaches_dispatch(monkeypatch):
    """The override steers a real op: forcing ``jnp`` on the update op
    must produce the bit-level woodbury result even if the process would
    otherwise pick a different default."""
    ainv, gs = _case(5, 40, 9)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jnp")
    got = nucb_update(ainv, gs)
    assert np.array_equal(np.asarray(got),
                          np.asarray(NU.woodbury_update(ainv, gs)))
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    got_i = nucb_update(ainv, gs)
    np.testing.assert_allclose(
        np.asarray(got_i), np.asarray(NU.sherman_morrison_batch(ainv, gs)),
        atol=SM_ATOL, rtol=0)
