"""A decode step's float32 state by slot moved on by one token, as a Pallas
TPU kernel over the whole state where it lies.

The continuous-batching engine (ray_tpu/llm/continuous.py) keeps a
recurrent layer's state a head by slot, ``S`` ``[layers, B, H, dk, dv]``
in float32, for the gated delta rule (``delta``) and for the Mamba-2 mixer
of a parallel layer (``ssm``). Each decode step moves every live slot's
``S`` on by its token (``transformer.delta_step``) and reads the layer's
output off the moved state. As XLA fusions that is the state read two or
three times a layer (the readout, the delta rule's correction, the
select that keeps an idle slot's rows) and written once; this kernel reads
each block of ``S`` once and writes it once, in place.

Grid: slots x blocks of heads. The state ``[layers, B, H, dk, dv]`` is the
operand whole and its result aliases it; the layer comes by scalar
prefetch, so no slice of a layer (a quarter of a GiB at 64 slots of 32
heads of 256 x 128) is copied out and back. A program's block is
``heads_per_block`` heads of one slot's layer, brought to VMEM and written
back by the pipeline while the block before it is computed. Per head:
``S' = exp(g) S``; with ``beta`` ``u = beta (v - S'^T k)``, without it ``u
= v``; ``S = S' + k u^T``; ``o = S^T q``, all float32 products and sums
over ``dk`` on the vector unit: nothing is rounded, as in ``delta_step``. A
slot that is not active writes back the rows it read, bit for bit, and its
output is what its rows give, which no caller reads.

q and k come as rows ``[heads, dk]`` a block and are turned into columns
once a block (a transpose of 32-bit words, exact), so each head's ``k`` and
``q`` broadcast along the lanes of its ``[dk, dv]`` tile. ``exp(g)`` and
``beta`` are one scalar a head, read from SMEM a slot at a time.

Numerics are checked against ``delta_step`` in interpret mode
(tests/test_state_step.py), the served tokens against the engine's jnp path
(tests/test_parallel_ssm_engine.py, tests/test_delta_state_engine.py), and
the kernel is compiled inside the decode step for a described v5e at both
deployments' geometries (tests/test_chip_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what a block of ``S`` may take of VMEM, counted four times over: the
#: pipeline holds two blocks in and two out. A row of ``dv`` takes whole
#: tiles of 128 lanes (192 -> 256), ``dk`` whole groups of 8 sublanes
STATE_BUFFER_BYTES = 16 * 2**20
#: VMEM the kernel may take beyond its four blocks: the small operands, a
#: head's temporaries
VMEM_SPARE_BYTES = 8 * 2**20


def _tile(n: int, to: int) -> int:
    return -(-n // to) * to


def heads_per_block(heads: int, dk: int, dv: int) -> int:
    """The heads of one slot a program moves on: the most that divide
    ``heads`` and whose block of ``S``, as the chip lays it out, fits four
    times in ``STATE_BUFFER_BYTES``; one head at least."""
    one = _tile(dk, 8) * _tile(dv, 128) * 4
    fits = max(1, STATE_BUFFER_BYTES // (4 * one))
    return max(h for h in range(1, min(heads, fits) + 1) if heads % h == 0)


def _step_kernel(
    layer_ref,  # int32[1] in SMEM (prefetched): the layer within the state
    active_ref,  # int32[B] in SMEM (prefetched): 1 for a live slot
    decay_ref,  # float32[1, hb] in SMEM: exp(g) of the block's heads
    *refs,
    delta: bool,
):
    del layer_ref  # the index maps read it
    if delta:
        beta_ref, q_ref, k_ref, v_ref, s_ref, o_ref, out_ref = refs
    else:
        q_ref, k_ref, v_ref, s_ref, o_ref, out_ref = refs
    live = active_ref[pl.program_id(0)] != 0
    qt = q_ref[...].T  # [dk, hb]: a head's q and k as columns
    kt = k_ref[...].T
    for h in range(s_ref.shape[0]):
        held = s_ref[h]  # [dk, dv]
        s = held * decay_ref[0, h]
        k = kt[:, h : h + 1]  # [dk, 1]
        u = v_ref[h : h + 1, :]  # [1, dv]
        if delta:
            u = beta_ref[0, h] * (u - jnp.sum(k * s, axis=0, keepdims=True))
        s = s + k * u
        o_ref[h : h + 1, :] = jnp.sum(qt[:, h : h + 1] * s, axis=0, keepdims=True)
        out_ref[h] = jnp.where(live, s, held)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_step_decode(
    state: jax.Array,  # float32 [L, B, H, dk, dv], every layer's
    layer: jax.Array,  # int32 scalar: the layer to move on
    active: jax.Array,  # bool [B]: slots whose rows move on
    q: jax.Array,  # [B, H, dk]
    k: jax.Array,  # [B, H, dk]
    v: jax.Array,  # [B, H, dv]
    g: jax.Array,  # [B, H]: the log of the decay
    beta: jax.Array | None = None,  # [B, H], or None: u = v
    *,
    interpret: bool = False,
):
    """``transformer.delta_step`` on row ``layer`` of ``state``, for every
    active slot, in place. Returns (o float32 [B, H, dv], the state with
    that layer's active slots moved on and every other row as it was); the
    state is its result's buffer, so a caller that donates it (or carries it
    through a loop) has nothing copied."""
    _, b, heads, dk, dv = state.shape
    hb = heads_per_block(heads, dk, dv)
    n = heads // hb
    f32 = jnp.float32

    def blocks(x):  # [B, H, size] -> [B, n, hb, size]
        return x.astype(f32).reshape(b, n, hb, x.shape[-1])

    def one_a_head(x):  # [B, H] -> [B, n, 1, hb]
        return x.astype(f32).reshape(b, n, 1, hb)

    squeeze = pl.Squeezed()

    def rows(size):
        return pl.BlockSpec(
            (squeeze, squeeze, hb, size), lambda i, j, *_: (i, j, 0, 0)
        )

    # a block's last two dimensions are the array's: [B, n, 1, hb]
    scalars = pl.BlockSpec(
        (squeeze, squeeze, 1, hb), lambda i, j, *_: (i, j, 0, 0),
        memory_space=pltpu.SMEM,
    )
    of_state = pl.BlockSpec(
        (squeeze, squeeze, hb, dk, dv),
        lambda i, j, layer, _: (layer[0], i, j, 0, 0),
    )
    operands = [one_a_head(jnp.exp(g.astype(f32)))]
    specs = [scalars]
    if beta is not None:
        operands.append(one_a_head(beta))
        specs.append(scalars)
    operands += [blocks(q), blocks(k), blocks(v), state]
    specs += [rows(dk), rows(dk), rows(dv), of_state]
    block = hb * _tile(dk, 8) * _tile(dv, 128) * 4
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, delta=beta is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n),
            in_specs=specs,
            out_specs=[rows(dv), of_state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n, hb, dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands count the two prefetched scalars: the state is the last
        input_output_aliases={2 + len(operands) - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * block + VMEM_SPARE_BYTES,
        ),
        name="state_step_decode",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        active.astype(jnp.int32),
        *operands,
    )
    return o.reshape(b, heads, dv), state
