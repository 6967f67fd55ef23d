"""Paged-attention decode kernel numerics vs the XLA gather reference
(interpret mode on CPU, same strategy as test_flash_attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.paged_attention import (
    paged_attention_decode,
    paged_attention_reference,
)


def _setup(b=4, kh=2, g=2, dk=32, dv=None, layers=3, n_pages=24, page=8,
           p_max=6, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    dv = dv or dk
    q = jax.random.normal(ks[0], (b, kh, g, dk), jnp.float32)
    k_pool = jax.random.normal(
        ks[1], (layers, kh, n_pages, page, dk), jnp.float32
    )
    v_pool = jax.random.normal(
        ks[2], (layers, kh, n_pages, page, dv), jnp.float32
    )
    tables = jax.random.randint(ks[3], (b, p_max), 0, n_pages, jnp.int32)
    return q, k_pool, v_pool, tables


def _both(q, k_pool, v_pool, layer, tables, lengths, scale, chunk=2):
    """The kernel, interpreted, two pages a chunk (so that slots of one,
    two and three chunks all occur), and the reference."""
    args = (q, k_pool, v_pool, jnp.int32(layer), tables,
            jnp.asarray(lengths, jnp.int32))
    want = paged_attention_reference(*args, scale=scale)
    got = paged_attention_decode(
        *args, scale=scale, pages_per_chunk=chunk, interpret=True
    )
    assert got.dtype == jnp.float32 and got.shape == want.shape
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize(
    "lengths",
    [
        (5, 17, 32, 1),     # ragged; one position
        (8, 16, 48, 24),    # exactly full pages, a full table
        (0, 41, 0, 9),      # inactive slots, the first among them
        (33, 0, 0, 0),      # a live slot, then none to hand the turn to
    ],
    ids=["ragged", "full-pages", "inactive", "trailing-idle"],
)
@pytest.mark.parametrize("g", [2, 4, 16])
def test_matches_reference(lengths, g):
    q, kp, vp, tables = _setup(g=g)
    got, want = _both(q, kp, vp, 1, tables, lengths, 32**-0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for slot, n in enumerate(lengths):
        if n == 0:  # an inactive slot walks nothing and gives zeros
            assert not got[slot].any()


def test_keys_stored_wider_than_the_head_and_than_the_values():
    """`mixed`'s full class in small: keys of 24 stored 32 wide (zeros
    behind), values 16 wide, and the scale the head's own."""
    q, kp, vp, tables = _setup(kh=2, g=4, dk=32, dv=16, seed=5)
    zeros = jnp.arange(32) >= 24
    q, kp = jnp.where(zeros, 0.0, q), jnp.where(zeros, 0.0, kp)
    lengths = (7, 30, 48, 16)
    got, want = _both(q, kp, vp, 2, tables, lengths, 24**-0.5)
    assert got.shape == (4, 2, 4, 16)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the stored width's scale is another answer
    other, _ = _both(q, kp, vp, 2, tables, lengths, 32**-0.5)
    assert np.abs(other - want).max() > 1e-3


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_layer_is_an_operand(layer):
    """One pool of several layers, handed over whole: the kernel reads the
    layer it is told, and another layer's pages are another answer."""
    q, kp, vp, tables = _setup(seed=11)
    lengths = (24, 3, 40, 11)
    got, want = _both(q, kp, vp, layer, tables, lengths, 32**-0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    _, other = _both(q, kp, vp, (layer + 1) % 3, tables, lengths, 32**-0.5)
    assert np.abs(other - want).max() > 1e-2


def test_page_sharing_between_slots():
    """Two slots whose tables point at the SAME physical pages (prefix
    sharing) must read identical data."""
    q, kp, vp, tables = _setup(seed=7)
    shared = tables.at[1].set(tables[0])
    q = q.at[1].set(q[0])  # same query + same pages -> same output
    got, want = _both(q, kp, vp, 0, shared, (24, 24, 9, 3), 32**-0.5)
    np.testing.assert_allclose(got[0], got[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_bfloat16_pool_as_deployed():
    """K and V read as bfloat16, scores and sums in float32: against the
    reference on the same rounded operands, the difference is the
    probabilities' rounding to bfloat16 for the second product."""
    q, kp, vp, tables = _setup(g=4, dk=128, page=16, seed=3)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    got, want = _both(q, kp, vp, 1, tables, (90, 0, 16, 33), 128**-0.5, chunk=4)
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-3)
