"""The decode step's state kernel (``ops/state_step.py``) against
``transformer.delta_step``, interpreted: the delta rule (``beta`` given)
and the Mamba-2 mixer's diagonal transition (``beta`` None) at both
deployments' widths a head, ``falcon-h1-34b-l6``'s 256 x 128 and
``olmo-hybrid-7b-l16``'s 96 x 192, with an odd number of heads, a batch
that is no power of two and slots that are not active, on a middle layer of
a state of three. The moved rows and the output agree to float32's
rounding (the kernel sums over ``dk`` in another order); every other row
of the buffer, other layers' and idle slots', is what it was, bit for bit.
The compiled kernel inside ``decode_step`` is ``tests/test_chip_compile.py``'s.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.ops import state_step
from ray_tpu.ops.state_step import heads_per_block, state_step_decode

LAYERS, SLOTS, HEADS, LAYER = 3, 5, 3, 1
ACTIVE = np.array([True, False, True, True, False])


def operands(dk, dv, delta, heads=HEADS, qk_dtype=jnp.float32, seed=0):
    """A state of three layers, and one token a slot as ``gated_delta``
    or ``mamba2`` hands it over: q and k to unit length, a gate below 0, a
    beta in (0, 2)."""
    r = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    state = r.normal(size=(LAYERS, SLOTS, heads, dk, dv)).astype(np.float32)
    q = unit(r.normal(size=(SLOTS, heads, dk))) * dk**-0.5
    k = unit(r.normal(size=(SLOTS, heads, dk)))
    v = r.normal(size=(SLOTS, heads, dv))
    g = -r.exponential(size=(SLOTS, heads))
    beta = 2 * r.random((SLOTS, heads)) if delta else None
    return (
        jnp.asarray(state), jnp.asarray(q, qk_dtype), jnp.asarray(k, qk_dtype),
        jnp.asarray(v, jnp.float32), jnp.asarray(g, jnp.float32),
        None if beta is None else jnp.asarray(beta, jnp.float32),
    )


def close(got, want):
    """Within float32's rounding of a sum over a head's ``dk`` terms."""
    scale = np.abs(want).max()
    np.testing.assert_allclose(
        got, want, rtol=0, atol=4 * np.finfo(np.float32).eps * 256 * scale
    )


def check(state, q, k, v, g, beta):
    o, moved = state_step_decode(
        state, jnp.int32(LAYER), jnp.asarray(ACTIVE), q, k, v, g, beta,
        interpret=True,
    )
    want_o, want_s = tfm.delta_step(state[LAYER], q, k, v, g, beta)
    before, after = np.asarray(state), np.asarray(moved)
    assert o.shape == v.shape and o.dtype == jnp.float32
    assert after.dtype == np.float32
    close(np.asarray(o)[ACTIVE], np.asarray(want_o)[ACTIVE])
    close(after[LAYER][ACTIVE], np.asarray(want_s)[ACTIVE])
    # the live rows did move, and nothing else did
    assert np.abs(after[LAYER][ACTIVE] - before[LAYER][ACTIVE]).max() > 1e-3
    np.testing.assert_array_equal(after[LAYER][~ACTIVE], before[LAYER][~ACTIVE])
    others = [i for i in range(LAYERS) if i != LAYER]
    np.testing.assert_array_equal(after[others], before[others])


@pytest.mark.parametrize("delta", [False, True], ids=["mamba2", "delta_rule"])
@pytest.mark.parametrize(
    "dk, dv", [(256, 128), (96, 192)], ids=["falcon_256x128", "olmo_96x192"]
)
def test_the_kernel_moves_the_state_as_delta_step_does(dk, dv, delta):
    check(*operands(dk, dv, delta))


def test_bfloat16_q_and_k_are_taken_as_delta_step_takes_them():
    """``gated_delta`` hands q and k over in the served type: raised to
    float32 exactly, as ``delta_step``'s products with the float32 state
    raise them."""
    check(*operands(96, 192, True, qk_dtype=jnp.bfloat16, seed=1))


def test_several_blocks_of_heads_a_slot(monkeypatch):
    """Where a slot's heads do not fit one block (six heads, two a block:
    three programs a slot), each program moves its own heads and no
    other's."""
    one = 16 * 128 * 4  # a head of 16 x 128 as the chip lays it out
    monkeypatch.setattr(state_step, "STATE_BUFFER_BYTES", 4 * 2 * one)
    assert heads_per_block(6, 16, 128) == 2
    for delta in (False, True):
        check(*operands(16, 128, delta, heads=6, seed=2))


def test_the_heads_of_a_block_come_from_the_states_shape():
    """The most heads that divide the slot's and whose block, rows of
    ``dv`` in whole tiles of 128 lanes, fits four times (two blocks in,
    two out) in the budget; one head at least."""
    assert heads_per_block(32, 256, 128) == 32  # think: 4 MiB a block
    assert heads_per_block(30, 96, 192) == 30  # longdoc: 192 takes 256 lanes
    assert heads_per_block(64, 256, 128) == 32
    assert heads_per_block(30, 256, 512) == 6  # fits 8: 6 divides 30
    assert heads_per_block(7, 512, 1024) == 1  # fits 2: only 1 divides 7
    for heads, dk, dv in [(32, 256, 128), (30, 96, 192), (64, 256, 128)]:
        block = heads_per_block(heads, dk, dv) * dk * (-(-dv // 128) * 128) * 4
        assert 4 * block <= state_step.STATE_BUFFER_BYTES
