"""Switch-style Mixture-of-Experts with expert parallelism over ``tp``.

The reference only forwards ``expert_parallel_size`` to vLLM (SURVEY §2.3).
Here EP is native: expert weight stacks carry a leading E axis sharded over
the mesh ``tp`` axis, and dispatch is the GShard dense-einsum formulation
(one-hot dispatch/combine tensors — static shapes, MXU-friendly; XLA turns
the einsums into an all-to-all across the expert axis). Top-1 routing with
capacity dropping, Switch-Transformer style.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def init_moe(n_experts: int, d_model: int, d_ff: int, n_layers: int, key, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = d_model**-0.5
    s_ff = d_ff**-0.5

    def init(k, *shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    return {
        "router": init(k1, n_layers, d_model, n_experts, scale=s_in),
        "w_gate": init(k2, n_layers, n_experts, d_model, d_ff, scale=s_in),
        "w_up": init(k3, n_layers, n_experts, d_model, d_ff, scale=s_in),
        "w_down": init(k4, n_layers, n_experts, d_ff, d_model, scale=s_ff),
    }


def moe_specs(lp):
    """Experts sharded over tp (= the EP axis); router replicated."""
    return {
        "router": P(lp, None, None),
        "w_gate": P(lp, "tp", None, None),
        "w_up": P(lp, "tp", None, None),
        "w_down": P(lp, "tp", None, None),
    }


def moe_apply(p, x: jax.Array, capacity_factor: float = 1.25) -> jax.Array:
    """x: [B, T, D] -> [B, T, D]."""
    b, t, d = x.shape
    n = b * t
    e = p["router"].shape[-1]
    cap = max(1, int(capacity_factor * n / e))
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)          # [N, E]
    gate = jnp.max(probs, axis=-1)                    # [N]
    expert = jnp.argmax(probs, axis=-1)               # [N]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # [N, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0   # position within expert
    keep = (pos >= 0) & (pos < cap)
    pos_clipped = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    # dispatch[n, e, c] — GShard dense dispatch tensor
    dispatch = (
        onehot * keep
    )[:, :, None] * jax.nn.one_hot(pos_clipped, cap, dtype=jnp.float32)
    combine = dispatch * gate[:, None, None]

    xin = jnp.einsum("nec,nd->ecd", dispatch, xf.astype(jnp.float32)).astype(
        x.dtype
    )
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", xin, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])
    out = jnp.einsum("nec,ecd->nd", combine, y.astype(jnp.float32))
    return out.astype(x.dtype).reshape(b, t, d)


# ---------------------------------------------------------------------------
# Dropless top-k experts over a published router width, for a holder of
# some of the experts (the paged serving engine, llm/continuous.py). The
# Switch layer above stays what the train step runs.
# ---------------------------------------------------------------------------


# what an expert layer holds for each held expert; `router` and
# `router_bias` are the layer's own
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def init_experts(n_routed: int, held: int, d_model: int, d_ff: int,
                 n_layers: int, key, dtype):
    """Weights of ``n_layers`` expert layers that hold ``held`` of
    ``n_routed`` experts: the router keeps its published width."""
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def init(k, *shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    return {
        "router": init(k1, n_layers, d_model, n_routed, scale=d_model**-0.5),
        # the correction bias chooses and does not weigh (noaux_tc)
        "router_bias": jnp.zeros((n_layers, n_routed), jnp.float32),
        "w_gate": init(k2, n_layers, held, d_model, d_ff, scale=d_model**-0.5),
        "w_up": init(k3, n_layers, held, d_model, d_ff, scale=d_model**-0.5),
        "w_down": init(k4, n_layers, held, d_ff, d_model, scale=d_ff**-0.5),
    }


def route(p, x: jax.Array, top_k: int, norm_eps: float = 0.0,
          scale: float = 1.0):
    """Sigmoid scores and selection in float32. x: [N, D]. Returns the
    chosen experts' ids [N, k] over the router's whole width and their
    weights [N, k]: the ``top_k`` largest of score + bias, weighed by the
    score alone, normalised over all the chosen (held by this holder or
    not; ``norm_eps`` under the sum), times ``scale``."""
    logits = jnp.matmul(
        x.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    weights = weights / total
    return chosen, weights if scale == 1.0 else weights * scale


def gmm_tiles(rows: int, k: int, n: int) -> tuple[int, int, int]:
    """Megablox ``gmm``'s (m, k, n) tiles for a product of ``rows`` x
    ``k`` by ``[groups, k, n]``, from those shapes alone: each the largest
    piece that divides its dimension, rows in multiples of 8 up to 128,
    ``k`` in multiples of 128 up to 2,048 and ``n`` up to 1,024 (wider
    than 512 lanes a decode layer's products read their weights 2 to 18 %
    faster on a v5e; PERF.md section 6). A dimension no such piece
    divides is one whole tile (a block as long as its array is always
    legal)."""

    def piece(size, cap, align):
        fits = range(align, min(size, cap) + 1, align)
        return max((t for t in fits if size % t == 0), default=size)

    return piece(rows, 128, 8), piece(k, 2048, 128), piece(n, 1024, 128)


def experts_apply(p, x: jax.Array, *, top_k: int, held, live=None,
                  norm_eps: float = 0.0, scale: float = 1.0, layer=None,
                  kernel=None):
    """This holder's part of an expert layer: ``sum of w_e * SwiGLU_e(x)``
    over the experts a token chose that are held here, experts
    ``held[0] .. held[0] + held[1] - 1`` of the router's width. No token
    is dropped and nothing stands in for the experts held elsewhere: with
    ``held = (0, n_routed)`` this is the whole layer, and the parts of
    all holders add up to it.

    x: [N, D]; ``live``: bool[N], tokens that count (a decode step's
    inactive slots choose nothing); ``norm_eps`` and ``scale`` are the
    router's (``route``). ``p``: the layer's ``router`` and
    ``router_bias``, and ``EXPERT_WEIGHTS`` either the layer's own
    ``[held, D, F]`` or, with ``layer`` (an int32 scalar, traced under a
    scan), the ``[L, held, D, F]`` stacks of a run of ``L`` layers of
    which this is the ``layer``-th. Token-expert pairs are sorted by held
    expert and go through one grouped matmul (``lax.ragged_dot``: on the
    TPU a kernel that visits the rows of each group with that group's
    weights, so the kernel itself skips an expert no token chose). The
    grouped matmul reads a run's stack where it lies: the stack is viewed
    as ``L * held`` groups (a reshape of leading dims, no copy) and the
    group sizes are zero outside the layer's ``held``, so the layers that
    are not this one are so many experts no token chose. Handed the
    layer's slice of the stack instead, as a ``lax.scan`` over the stack
    hands it, the chip's compiler wrote the slice out as a buffer of its
    own before the kernel read it, every held expert chosen or not: a
    third of both sparse cells' decode step (PERF.md section 6, PR 39).
    The rows are a static budget: twice what a uniform router sends here,
    and all ``N * top_k`` pairs in the branch taken when more than that
    arrive.

    ``kernel``: None for ``lax.ragged_dot``; ``"compiled"`` or
    ``"interpret"`` for Megablox ``gmm`` (a Pallas kernel, compiled for
    the TPU or interpreted) over the same rows, groups and stack, tiled by
    ``gmm_tiles``. At a decode step's few rows ``ragged_dot`` read the
    experts at half of HBM's rate and ``gmm`` near it (PERF.md section
    6). Both take the operands' precision and accumulate
    in float32.

    Returns (out [N, D], pairs held here, held experts hit), the last two
    int32 counts over live tokens."""
    n, d = x.shape
    first, count = held
    n_routed = p["router"].shape[-1]
    chosen, weights = route(p, x, top_k, norm_eps, scale)
    local = chosen - first
    here = (local >= 0) & (local < count)
    if live is not None:
        here = here & live[:, None]
    # pairs held elsewhere sort behind every held expert's
    group = jnp.where(here, local, count).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=count + 1)[:count].astype(jnp.int32)
    n_pairs = jnp.sum(sizes)
    token = (jnp.arange(n * top_k, dtype=jnp.int32) // top_k)[order]
    weight = jnp.where(here, weights, 0.0).reshape(-1)[order]
    stacks = [p[name] for name in EXPERT_WEIGHTS]
    if layer is None:  # the layer's own weights: a stack of one
        stacks, layer = [w[None] for w in stacks], 0
    groups = stacks[0].shape[0] * count
    w_gate, w_up, w_down = (w.reshape(groups, *w.shape[2:]) for w in stacks)
    in_stack = jax.lax.dynamic_update_slice(
        jnp.zeros((groups,), jnp.int32), sizes, (layer * count,)
    )

    def grouped(lhs, rhs):
        if kernel is None:
            return jax.lax.ragged_dot(lhs, rhs, in_stack)
        from jax.experimental.pallas.ops.tpu import megablox

        return megablox.gmm(
            lhs, rhs, in_stack, preferred_element_type=lhs.dtype,
            tiling=gmm_tiles(*lhs.shape, rhs.shape[-1]),
            interpret=kernel == "interpret",
        )

    def run(rows: int):
        tok, w = token[:rows], weight[:rows]
        xs = x[tok]
        gate = grouped(xs, w_gate)
        up = grouped(xs, w_up)
        y = grouped(jax.nn.silu(gate) * up, w_down)
        # rows past the last group belong to no expert: whatever the
        # kernel left there is not read
        y = jnp.where(
            (jnp.arange(rows) < n_pairs)[:, None], y.astype(jnp.float32), 0.0
        )
        out = jnp.zeros((n, d), jnp.float32).at[tok].add(y * w[:, None])
        return out.astype(x.dtype)

    every = n * top_k
    usual = -(-2 * every * count // n_routed)
    usual = min(every, -(-usual // 8) * 8)
    if usual == every:
        out = run(every)
    else:
        out = jax.lax.cond(
            n_pairs <= usual, lambda: run(usual), lambda: run(every)
        )
    return out, n_pairs, jnp.sum(sizes > 0).astype(jnp.int32)
