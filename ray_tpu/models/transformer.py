"""Flagship model: LLaMA-style decoder, TPU-first.

Design (vs the reference, which wraps vLLM/torch and has no native model):

- Pure-functional pytree params; layer weights stacked on a leading axis so
  the forward is a ``lax.scan`` over layers (one compile of one block).
- bfloat16 compute, fp32 RMSNorm/softmax accumulators (MXU-friendly).
- 4D parallelism on the canonical mesh (parallel/mesh.py):
  * dp — batch sharding (gradient psum inserted by XLA),
  * tp — Megatron-style head/hidden sharding via parameter PartitionSpecs,
  * pp — GPipe microbatching over ppermute (ops/pipeline.py),
  * sp — ring attention over ppermute (ops/ring_attention.py),
  * ep — MoE experts sharded over the tp axis (models/moe.py).
- Under jit the whole train step is one XLA program; pp/sp sections run
  manual (shard_map axis_names={'pp','sp'}), dp/tp stay auto.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.layers import (
    apply_rope,
    attention_reference,
    rms_norm,
    rope_freqs,
    swiglu,
)
from ray_tpu.ops.pipeline import pipeline_apply
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.models import moe as moe_mod
from ray_tpu.util.compile_cache import configure_compile_cache


class UnsupportedModelFeature(NotImplementedError):
    """A path was handed a configuration with a field it does not
    implement (a layer pattern on the train step, a window class of KV
    page in the prefix cache, ...). Raised where the path is entered, by
    the field's name: nothing computes something else instead."""


class StackLayoutError(ValueError):
    """``params["blocks"]`` of a stack by position is not laid out as the
    program reads it: one stack of weights for each run of consecutive
    layers of one kind (``ModelConfig.layer_runs``), ``count`` layers
    each. Weights stacked by kind of layer, several runs in one stack,
    are that of a kind with one run only."""


@dataclass(frozen=True)
class AttnKind:
    """What one kind of token mixer fixes. ``name`` is also the name of
    what its layers keep for a sequence (``PagedKVPool``): a class of KV
    page for the two kinds of attention; for ``conv`` (a gated short
    convolution, no K and V) a few columns of state by slot; for ``delta``
    (the gated delta rule, a linear attention) such columns and a matrix of
    state a head. ``state`` names the state by slot a layer of the kind
    keeps, "" for none: ``conv`` and ``delta`` keep it instead of pages;
    ``ssm``, a Mamba-2 mixer run beside the attention over the same input
    (Falcon-H1's parallel block, ``attn_pattern`` "parallel"), beside the
    pages of the attention's class."""
    name: str            # "full" | "window" | "conv" | "delta"
    kv_heads: int
    rope_theta: float    # 0 = the layer's q and k are not rotated
    window: int          # 0 = every earlier key
    sink: bool           # a learned per-head column that takes mass only
    state: str = ""      # "" | "conv" | "delta" | "ssm"


CONV = AttnKind("conv", 0, 0.0, 0, False, "conv")
DELTA = AttnKind("delta", 0, 0.0, 0, False, "delta")
# the kinds that are no attention: state by slot, no K and V, no rotary
STATE_KINDS = {"conv": CONV, "delta": DELTA}
# tokens a block of the chunked scan holds (``delta_scan``: the delta rule's
# and the Mamba-2 mixer's)
DELTA_BLOCK = 64


@dataclass(frozen=True)
class LayerRun:
    """Consecutive layers of one kind: the ``count`` layers of the run's
    own stack of weights (``key`` in ``params["blocks"]``; ``None`` where
    the stack is uniform and ``blocks`` is the one stack), and where the
    run's first layer lies in what its kind keeps for a sequence (its
    class of KV page, or the convolution layers' state) and, of a kind
    that keeps state by slot, in that state (``state_start``: where a
    parallel layer's row of the Mamba-2 state lies; for ``conv`` and
    ``delta`` it is ``cache_start``)."""
    attn: AttnKind
    experts: bool
    key: Optional[str]
    count: int
    cache_start: int
    state_start: int = 0

    @property
    def start(self) -> int:
        """Row of the run's first layer in its stack: 0, since every run
        has a stack of its own (``benchmarks/tests/test_fault_toy.py`` pins
        the name; a ``benchmark`` PR can drop it there and here)."""
        return 0


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1408            # width of a dense feed-forward layer
    max_seq_len: int = 2048
    rope_theta: float = 10000.0  # 0 = no rotary: q and k carry no position
    n_experts: int = 0          # 0 = dense MLP; >0 = Switch-MoE every layer
    expert_capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    # sequence-parallel attention flavor: "ring" (ppermute KV rotation,
    # ops/ring_attention.py) or "ulysses" (all-to-all head/sequence swap,
    # ops/ulysses.py) — both net-new vs the reference (SURVEY §2.3).
    sp_attention: str = "ring"
    # rematerialize each block in the backward pass (jax.checkpoint) —
    # trades ~1/3 extra FLOPs for O(n_layers) less residual HBM. The
    # standard TPU memory lever for deep/long-sequence configs.
    remat: bool = False
    rms_eps: float = 1e-6
    # -- attention: sizes that need not follow from d_model / n_heads ------
    head_dim: int = 0           # 0 = d_model // n_heads
    v_head_dim: int = 0         # 0 = head_dim
    rotary_dim: int = 0         # leading dims of a head that rotate; 0 = all
    value_scale: float = 1.0    # values are scaled before attention
    # -- the stack by position. Empty patterns: every layer full attention
    # and a dense feed-forward, one uniform stack (the defaults above) ----
    attn_pattern: Tuple[str, ...] = ()    # "full" | "window" | "conv" | "delta" | "parallel", one a layer
    ffn_pattern: Tuple[str, ...] = ()     # "dense" | "experts", one a layer
    window: int = 0                       # keys a windowed query sees, itself among them
    window_kv_heads: int = 0              # 0 = n_kv_heads
    window_rope_theta: float = 0.0        # 0 = rope_theta
    window_sink: bool = False
    qk_norm: bool = False                 # RMS norm over each head of q and k, before the rotary
    qk_norm_whole: bool = False           # ... over the whole projection instead, all heads as one row
    post_norm: bool = False               # x + norm(op(x)): ln1 and ln2 after operator and feed-forward, none before
    conv_kernel: int = 0                  # taps of a "conv" or "delta" layer's causal depthwise convolution
    tie_embeddings: bool = False          # the head is the embedding, transposed
    # -- "delta" layers (``gated_delta``): heads, and a head's key and value size
    delta_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_neg_eigval: bool = False        # beta in (0, 2): the transition's eigenvalue 1 - beta in (-1, 1)
    # -- "parallel" layers (``mamba2`` beside the attention): the Mamba-2
    # mixer's heads, a head's size, the state's size, groups of B and C
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    # -- muP multipliers (Falcon-H1); 1 = none, and nothing is multiplied
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0           # on k before the rotary
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = ()   # z, x, B, C, dt of the mixer's input projection
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)   # gate before the SiLU; after W_down
    # -- expert layers (models/moe.py experts_apply): dropless top-k over
    # the published router width, this holder's experts computed ----------
    d_ff_expert: int = 0
    n_routed_experts: int = 0
    experts_per_token: int = 0
    experts_held: Tuple[int, int] = (0, 0)   # (first, count); (0, 0) = all
    router_norm_eps: float = 0.0          # under the sum the chosen scores are normalised by
    routed_scaling: float = 1.0           # times the normalised weights

    def __post_init__(self):
        derived = {
            "head_dim": self.head_dim or self.d_model // self.n_heads,
            "attn_pattern": tuple(self.attn_pattern),
            "ffn_pattern": tuple(self.ffn_pattern),
            "experts_held": tuple(self.experts_held),
            "ssm_multipliers": tuple(self.ssm_multipliers),
            "mlp_multipliers": tuple(self.mlp_multipliers),
        }
        derived["v_head_dim"] = self.v_head_dim or derived["head_dim"]
        derived["rotary_dim"] = self.rotary_dim or derived["head_dim"]
        if derived["experts_held"] == (0, 0):
            derived["experts_held"] = (0, self.n_routed_experts)
        for name, value in derived.items():
            object.__setattr__(self, name, value)
        for name, allowed in (("attn_pattern", ("full", "window", "parallel",
                                                *STATE_KINDS)),
                              ("ffn_pattern", ("dense", "experts"))):
            pattern = derived[name]
            if pattern and (
                len(pattern) != self.n_layers or set(pattern) - set(allowed)
            ):
                raise ValueError(
                    f"{name} names one of {allowed} for each of "
                    f"{self.n_layers} layers, got {pattern}"
                )
        if "window" in derived["attn_pattern"] and self.window < 1:
            raise ValueError("a windowed layer needs `window` >= 1")
        if {*STATE_KINDS, "parallel"} & set(derived["attn_pattern"]) and (
            self.conv_kernel < 2
        ):
            raise ValueError(
                "a convolution, delta or parallel layer needs `conv_kernel` >= 2"
            )
        if "parallel" in derived["attn_pattern"] and not (
            self.ssm_heads > 0 and self.ssm_head_dim > 0 and self.ssm_state > 0
            and self.ssm_groups > 0 and self.ssm_heads % self.ssm_groups == 0
        ):
            raise ValueError(
                "a parallel layer needs ssm_heads, ssm_head_dim, ssm_state "
                "and ssm_groups, the groups dividing the heads"
            )
        if derived["ssm_multipliers"] and len(derived["ssm_multipliers"]) != 5:
            raise ValueError("ssm_multipliers gives z, x, B, C and dt one each")
        if "delta" in derived["attn_pattern"] and not (
            self.delta_heads > 0 and self.delta_key_dim > 0
            and self.delta_value_dim > 0
        ):
            raise ValueError(
                "a delta layer needs delta_heads, delta_key_dim and "
                "delta_value_dim"
            )
        if self.qk_norm_whole and not self.qk_norm:
            raise ValueError("`qk_norm_whole` says over what `qk_norm` runs")
        if "experts" in derived["ffn_pattern"]:
            first, count = derived["experts_held"]
            if not (
                0 < self.experts_per_token <= self.n_routed_experts
                and self.d_ff_expert > 0
                and 0 <= first and count > 0
                and first + count <= self.n_routed_experts
            ):
                raise ValueError(
                    "an expert layer needs d_ff_expert, n_routed_experts >= "
                    "experts_per_token > 0 and experts_held inside the "
                    f"router's width, got {self}"
                )
        if derived["rotary_dim"] % 2 or derived["rotary_dim"] > derived["head_dim"]:
            raise ValueError("rotary_dim is even and at most head_dim")

    @property
    def uniform(self) -> bool:
        """One kind of layer throughout: ``params["blocks"]`` is one
        stack, as every dense configuration's is."""
        return not self.attn_pattern and not self.ffn_pattern

    def attn_kind(self, name: str) -> AttnKind:
        if name in STATE_KINDS:
            return STATE_KINDS[name]
        if name == "window":
            return AttnKind(
                "window", self.window_kv_heads or self.n_kv_heads,
                self.window_rope_theta or self.rope_theta, self.window,
                self.window_sink,
            )
        return AttnKind(
            "full", self.n_kv_heads, self.rope_theta, 0, False,
            "ssm" if name == "parallel" else "",
        )

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(attention kind, feed-forward kind) of each layer."""
        attn = self.attn_pattern or ("full",) * self.n_layers
        ffn = self.ffn_pattern or ("dense",) * self.n_layers
        return tuple(zip(attn, ffn))

    def layer_runs(self) -> Tuple[LayerRun, ...]:
        """The stack as runs of consecutive layers of one kind, in order.
        Each run has a stack of weights of its own: ``<attention>.<feed-
        forward>`` names a kind's first run, ``<...>.<n>`` its n-th later
        one. (A run that was a slice of its kind's stack had the slice
        copied out every step by the chip's compiler: a ``lax.scan`` reads
        a whole buffer. What the scan hands its body is a slice too, and
        an expert run's three large stacks were copied layer by layer for
        the grouped matmul: ``run_stack`` keeps those out of the scan.)"""
        runs, last_kind, of_kind, in_class, in_state = [], None, {}, {}, {}
        for kind in self.layer_kinds():
            attn, ffn = kind
            mixer = self.attn_kind(attn)
            if runs and kind == last_kind:
                runs[-1] = dataclasses.replace(runs[-1], count=runs[-1].count + 1)
            else:
                n = of_kind.get(kind, 0)
                of_kind[kind] = n + 1
                key = f"{attn}.{ffn}" + (f".{n}" if n else "")
                runs.append(LayerRun(
                    mixer, ffn == "experts", None if self.uniform else key, 1,
                    in_class.get(mixer.name, 0), in_state.get(mixer.state, 0),
                ))
            last_kind = kind
            in_class[mixer.name] = in_class.get(mixer.name, 0) + 1
            if mixer.state:
                in_state[mixer.state] = in_state.get(mixer.state, 0) + 1
        return tuple(runs)

    def require_blocks_by_run(self, blocks) -> None:
        """Raises ``StackLayoutError`` where a stack by position's
        ``blocks`` are not one stack a run (``layer_runs``): by whoever
        takes weights (the engine as it is built, ``run_stack`` as it is
        traced), so that weights stacked another way fail by name and
        never as a ``KeyError`` or a scan over the wrong layers."""
        if self.uniform:
            return
        runs = {run.key: run.count for run in self.layer_runs()}
        have = {k: v["ln1"].shape[0] for k, v in blocks.items()}
        if have != runs:
            raise StackLayoutError(
                "`blocks` holds stacks (layers each) "
                f"{dict(sorted(have.items()))}, the pattern's runs are "
                f"{dict(sorted(runs.items()))}: the program reads one "
                "stack of weights for each run of consecutive layers of "
                "one kind (`ModelConfig.layer_runs`)"
            )

    def kv_classes(self) -> Dict[str, Tuple[int, AttnKind]]:
        """Classes of KV page, by the attention kind that writes them:
        name -> (layers of that kind, the kind). A convolution or delta
        layer writes none; a parallel layer writes the ``full`` class."""
        counts: Dict[str, int] = {}
        for attn, _ in self.layer_kinds():
            if attn not in STATE_KINDS:
                name = self.attn_kind(attn).name
                counts[name] = counts.get(name, 0) + 1
        return {
            n: (c, self.attn_kind(n)) for n, c in sorted(counts.items())
        }

    def state_kinds(self) -> Dict[str, int]:
        """The state by slot the layers keep, by name -> layers that keep
        it: ``conv``, ``delta`` (instead of pages), ``ssm`` (a parallel
        layer's, beside its pages)."""
        counts: Dict[str, int] = {}
        for attn, _ in self.layer_kinds():
            state = self.attn_kind(attn).state
            if state:
                counts[state] = counts.get(state, 0) + 1
        return dict(sorted(counts.items()))

    @property
    def state_layers(self) -> int:
        """Layers that keep state by slot."""
        return sum(self.state_kinds().values())

    def state_patterns(self) -> Tuple[str, ...]:
        """The ``attn_pattern`` entries whose layers keep state by slot."""
        return tuple(sorted({
            a for a, _ in self.layer_kinds() if self.attn_kind(a).state
        }))

    @property
    def delta_width(self) -> int:
        """Channels of a delta layer's short convolution: q, k and v of
        every head side by side."""
        return self.delta_heads * (2 * self.delta_key_dim + self.delta_value_dim)

    @property
    def ssm_inner(self) -> int:
        """The Mamba-2 mixer's x and z: heads x a head's size."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_width(self) -> int:
        """Channels of the Mamba-2 mixer's short convolution: x, B, C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def require_uniform_dense(self, path: str) -> None:
        """For the paths that run the one uniform block with heads of one
        size: the train step (``param_specs``, ``forward``,
        ``make_train_step``)."""
        for name in ("attn_pattern", "ffn_pattern"):
            if getattr(self, name):
                raise UnsupportedModelFeature(
                    f"{path} runs one uniform block; `{name}` is not "
                    "implemented there (the paged engine, "
                    "llm/continuous.py, serves a stack by position)"
                )
        derived = self.d_model // self.n_heads
        for name, plain in (("head_dim", derived), ("v_head_dim", derived),
                            ("rotary_dim", derived), ("value_scale", 1.0),
                            ("qk_norm", False), ("tie_embeddings", False),
                            ("post_norm", False),
                            ("ssm_multipliers", ()),
                            ("mlp_multipliers", (1.0, 1.0)),
                            *((m, 1.0) for m in MULTIPLIERS)):
            if getattr(self, name) != plain:
                raise UnsupportedModelFeature(
                    f"{path} does not implement `{name}`="
                    f"{getattr(self, name)}"
                )
        if not self.rope_theta:
            raise UnsupportedModelFeature(
                f"{path} always rotates q and k; `rope_theta`=0 (no rotary) "
                "is not implemented there"
            )


# the muP multipliers that are one number each (``ModelConfig``): what the
# train step refuses where one is not 1
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier",
)


def _dense_init(key, *shape, dtype, scale=None):
    scale = scale or shape[-2] ** -0.5  # fan-in
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    """Stacked-layer parameter pytree. A uniform stack keeps its layers
    under ``blocks``; a stack by position keeps one stack for each run of
    consecutive layers of one kind under ``blocks[<the run's key>]``
    (``layer_runs``)."""
    if not cfg.uniform:
        return _init_params_by_run(cfg, key)
    k = jax.random.split(key, 12)
    d, hd = cfg.d_model, cfg.head_dim
    L = cfg.n_layers
    dt = cfg.dtype

    def norm_init(*shape):
        return jnp.ones(shape, dt)

    dense_init = functools.partial(_dense_init, dtype=dt)

    blocks = {
        "ln1": norm_init(L, d),
        "ln2": norm_init(L, d),
        "wq": dense_init(k[0], L, d, cfg.n_heads * hd),
        "wk": dense_init(k[1], L, d, cfg.n_kv_heads * hd),
        "wv": dense_init(k[2], L, d, cfg.n_kv_heads * hd),
        "wo": dense_init(k[3], L, cfg.n_heads * hd, d),
    }
    if cfg.n_experts > 0:
        blocks["moe"] = moe_mod.init_moe(
            cfg.n_experts, d, cfg.d_ff, L, k[4], dt
        )
    else:
        blocks["w_gate"] = dense_init(k[5], L, d, cfg.d_ff)
        blocks["w_up"] = dense_init(k[6], L, d, cfg.d_ff)
        blocks["w_down"] = dense_init(k[7], L, cfg.d_ff, d)
    return {
        "embed": dense_init(k[8], cfg.vocab_size, d, scale=0.02),
        "blocks": blocks,
        "ln_f": norm_init(d),
        "head": dense_init(k[9], d, cfg.vocab_size),
    }


def _init_params_by_run(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    d, dt = cfg.d_model, cfg.dtype
    first, held = cfg.experts_held

    dense = functools.partial(_dense_init, dtype=dt)
    blocks = {}
    for i, run in enumerate(cfg.layer_runs()):
        k = jax.random.split(jax.random.fold_in(key, i), 10)
        kind, n = run.attn, run.count
        p = {"ln1": jnp.ones((n, d), dt), "ln2": jnp.ones((n, d), dt)}
        if kind.name == "conv":
            p["w_in"] = dense(k[0], n, d, 3 * d)
            p["conv"] = dense(k[1], n, cfg.conv_kernel, d)
            p["w_out"] = dense(k[3], n, d, d)
        elif kind.name == "delta":
            heads, wide = cfg.delta_heads, cfg.delta_heads * cfg.delta_value_dim
            kd = jax.random.split(k[9], 3)
            p["w_qkv"] = dense(k[0], n, d, cfg.delta_width)
            p["conv"] = dense(k[1], n, cfg.conv_kernel, cfg.delta_width)
            p["w_a"] = dense(k[2], n, d, heads)
            p["w_b"] = dense(k[3], n, d, heads)
            # Mamba-2's and the published layer's: A in (0, 16), dt in
            # [0.001, 0.1], log-uniform
            p["a_log"] = jnp.log(jax.random.uniform(
                k[4], (n, heads), jnp.float32, 1e-3, 16.0))
            dt_init = jnp.exp(jax.random.uniform(
                kd[0], (n, heads), jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
            p["dt_bias"] = dt_init + jnp.log(-jnp.expm1(-dt_init))
            p["w_g"] = dense(kd[1], n, d, wide)
            p["o_norm"] = jnp.ones((n, cfg.delta_value_dim), dt)
            p["wo"] = dense(kd[2], n, wide, d)
        else:
            p["wq"] = dense(k[0], n, d, cfg.n_heads * cfg.head_dim)
            p["wk"] = dense(k[1], n, d, kind.kv_heads * cfg.head_dim)
            p["wv"] = dense(k[2], n, d, kind.kv_heads * cfg.v_head_dim)
            p["wo"] = dense(k[3], n, cfg.n_heads * cfg.v_head_dim, d)
            if kind.state == "ssm":
                p.update(_init_mamba2(cfg, n, k[9]))
            if cfg.qk_norm:
                q_row = cfg.n_heads if cfg.qk_norm_whole else 1
                k_row = kind.kv_heads if cfg.qk_norm_whole else 1
                p["q_norm"] = jnp.ones((n, q_row * cfg.head_dim), dt)
                p["k_norm"] = jnp.ones((n, k_row * cfg.head_dim), dt)
        if kind.sink:
            p["sink"] = jax.random.normal(k[4], (n, cfg.n_heads), jnp.float32)
        if run.experts:
            p["moe"] = moe_mod.init_experts(
                cfg.n_routed_experts, held, d, cfg.d_ff_expert, n, k[5], dt
            )
        else:
            p["w_gate"] = dense(k[6], n, d, cfg.d_ff)
            p["w_up"] = dense(k[7], n, d, cfg.d_ff)
            p["w_down"] = dense(k[8], n, cfg.d_ff, d)
        blocks[run.key] = p
    k = jax.random.split(jax.random.fold_in(key, len(blocks)), 2)
    params = {
        "embed": dense(k[0], cfg.vocab_size, d, scale=0.02),
        "blocks": blocks,
        "ln_f": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense(k[1], d, cfg.vocab_size)
    return params


def _init_mamba2(cfg: ModelConfig, n: int, key: jax.Array) -> Dict[str, Any]:
    """A parallel layer's Mamba-2 mixer (``mamba2``), ``n`` layers:
    ``ssm_in`` (d -> z, x, B, C and dt side by side), the short convolution's
    taps (the oldest first) and bias over x, B, C, ``A_log``, ``dt_bias`` and
    ``D`` a head (float32), the gated norm's scale, ``ssm_out``."""
    d, dt, heads = cfg.d_model, cfg.dtype, cfg.ssm_heads
    k = jax.random.split(key, 6)
    dense = functools.partial(_dense_init, dtype=dt)
    # Mamba-2's: A in (1, 16), dt in [0.001, 0.1], log-uniform
    step = jnp.exp(jax.random.uniform(
        k[3], (n, heads), jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
    return {
        "ssm_in": dense(k[0], n, d, 2 * cfg.ssm_inner
                        + 2 * cfg.ssm_groups * cfg.ssm_state + heads),
        "ssm_conv": dense(k[1], n, cfg.conv_kernel, cfg.ssm_width),
        "ssm_conv_bias": jnp.zeros((n, cfg.ssm_width), dt),
        "ssm_a_log": jnp.log(jax.random.uniform(
            k[2], (n, heads), jnp.float32, 1.0, 16.0)),
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_d": jnp.ones((n, heads), jnp.float32),
        "ssm_norm": jnp.ones((n, cfg.ssm_inner), dt),
        "ssm_out": dense(k[4], n, cfg.ssm_inner, d),
    }


def param_specs(cfg: ModelConfig, pp: int = 1) -> Dict[str, Any]:
    """PartitionSpec tree: Megatron tp sharding; layer axis sharded over pp
    when pipelined (each stage holds its slice of the stack)."""
    cfg.require_uniform_dense("param_specs (the mesh's sharding rules)")
    lp = "pp" if pp > 1 else None
    blocks = {
        "ln1": P(lp, None),
        "ln2": P(lp, None),
        "wq": P(lp, None, "tp"),
        "wk": P(lp, None, "tp"),
        "wv": P(lp, None, "tp"),
        "wo": P(lp, "tp", None),
    }
    if cfg.n_experts > 0:
        blocks["moe"] = moe_mod.moe_specs(lp)
    else:
        blocks["w_gate"] = P(lp, None, "tp")
        blocks["w_up"] = P(lp, None, "tp")
        blocks["w_down"] = P(lp, "tp", None)
    return {
        "embed": P("tp", None),
        "blocks": blocks,
        "ln_f": P(None),
        "head": P(None, "tp"),
    }


def shard_params(params, cfg: ModelConfig, mesh: Mesh):
    pp = mesh.shape.get("pp", 1)
    specs = param_specs(cfg, pp)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, jnp.ndarray),
    )


def _causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Optional[Mesh] = None
) -> jax.Array:
    """Causal self-attention of one block outside the sp axis: the Pallas
    flash kernels wherever Mosaic compiles them (forward and backward,
    ragged lengths padded inside), the fused-XLA reference on the CPU
    backend, where a Pallas kernel could only be interpreted.

    Under a mesh the kernels run in a shard_map, batch split over dp and
    heads over tp: every (batch, head) attends on its own, so the split is
    exact, and it has to be spelled out because the partitioner refuses a
    Mosaic kernel unless every mesh axis is manual ("cannot be
    automatically partitioned"). Inside the pipeline's shard_map pp and sp
    are manual already; this one takes the axes that are left. Autodiff
    cannot carry residuals out of a shard_map nested that way (it stacks
    them over every varying axis, pp included, which Shardy rejects), so
    forward and backward are two shard_maps under one custom_vjp and the
    residuals cross as ordinary outputs. For the same reason nothing in
    the two bodies may be independent of their inputs — partial evaluation
    of the layer scan would split it off as a residual — so a ragged
    length is padded out here, not inside. KV heads that do not divide
    over tp stay whole on every tp shard. The reference needs none of
    this: the partitioner splits it by itself."""
    if jax.default_backend() == "cpu":
        return attention_reference(q, k, v, causal=True)
    from ray_tpu.ops import flash_attention as fa

    if mesh is None or mesh.size == 1:
        return fa.flash_attention(q, k, v, causal=True)
    ctx = jax.sharding.get_abstract_mesh()  # set inside an outer shard_map
    heads = "tp" if k.shape[2] % mesh.shape["tp"] == 0 else None
    spec = P("dp", None, heads, None)  # q, k, v, out and their cotangents
    lse_spec = P("dp", heads, None)
    sharded = functools.partial(
        jax.shard_map,
        mesh=mesh if ctx.empty else None,
        axis_names=set(mesh.axis_names) - set(ctx.manual_axes),
        check_vma=True,
    )
    sm_fwd = sharded(
        functools.partial(fa.flash_fwd, causal=True),
        in_specs=(spec, spec, spec),
        out_specs=(spec, lse_spec),
    )
    sm_bwd = sharded(
        functools.partial(fa.flash_bwd, causal=True),
        in_specs=(spec, spec, spec, spec, lse_spec, spec),
        out_specs=(spec, spec, spec),
    )

    @jax.custom_vjp
    def attend(q, k, v):
        return sm_fwd(q, k, v)[0]

    def attend_fwd(q, k, v):
        out, lse = sm_fwd(q, k, v)
        return out, (q, k, v, out, lse)

    attend.defvjp(attend_fwd, lambda res, do: sm_bwd(*res, do))
    t = q.shape[1]
    pad = fa.pad_len(t, t, True, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
    return attend(*(fa.pad_rows(x, pad) for x in (q, k, v)))[:, :t]


def _block(cfg: ModelConfig, p: Dict[str, jax.Array], h: jax.Array,
           angles: jax.Array, *, sp_manual: bool,
           mesh: Optional[Mesh] = None) -> jax.Array:
    """One decoder block. h: [B, T(_local), D]; angles already offset."""
    b, t, d = h.shape
    hd = cfg.head_dim
    x = rms_norm(h, p["ln1"], cfg.rms_eps)
    q = (x @ p["wq"]).reshape(b, t, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(b, t, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
    q = apply_rope(q, angles)
    k = apply_rope(k, angles)
    if sp_manual:
        if cfg.sp_attention == "ulysses":
            from ray_tpu.ops.ulysses import ulysses_attention

            attn = ulysses_attention(q, k, v, "sp", causal=True)
        else:
            attn = ring_attention(q, k, v, "sp", causal=True)
    else:
        attn = _causal_attention(q, k, v, mesh)
    h = h + attn.reshape(b, t, -1) @ p["wo"]
    x = rms_norm(h, p["ln2"], cfg.rms_eps)
    if cfg.n_experts > 0:
        y = moe_mod.moe_apply(p["moe"], x, cfg.expert_capacity_factor)
    else:
        y = swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    return h + y


def _scan_blocks(cfg: ModelConfig, blocks, h, angles, *, sp_manual: bool,
                 mesh: Optional[Mesh] = None):
    def body(h, layer_p):
        return (
            _block(cfg, layer_p, h, angles, sp_manual=sp_manual, mesh=mesh),
            None,
        )

    if cfg.remat:
        # prevent_cse=False: under lax.scan the CSE-prevention barriers
        # are redundant and only cost compile/runtime (jax.checkpoint doc)
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, blocks)
    return h


def forward(
    params,
    tokens: jax.Array,  # int32 [B, T]
    cfg: ModelConfig,
    mesh: Optional[Mesh] = None,
    *,
    num_microbatches: int = 0,
) -> jax.Array:
    """Logits [B, T, V]. Dispatches to plain / ring-SP / pipelined paths
    based on the mesh shape (pp/sp manual, dp/tp auto)."""
    cfg.require_uniform_dense("the train step's forward")
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    b, t = tokens.shape
    h = params["embed"][tokens].astype(cfg.dtype)
    angles_full = rope_freqs(cfg.head_dim, t, cfg.rope_theta)

    if pp == 1 and sp == 1:
        h = _scan_blocks(
            cfg, params["blocks"], h, angles_full, sp_manual=False, mesh=mesh
        )
    elif pp == 1:
        # sequence-parallel only: ring attention over sp
        def sp_body(blocks, h_loc):
            t_loc = h_loc.shape[1]
            off = jax.lax.axis_index("sp") * t_loc
            ang = jax.lax.dynamic_slice_in_dim(angles_full, off, t_loc)
            return _scan_blocks(cfg, blocks, h_loc, ang, sp_manual=True)

        h = jax.shard_map(
            sp_body,
            mesh=mesh,
            in_specs=(P(), P(None, "sp", None)),
            out_specs=P(None, "sp", None),
            axis_names={"sp"},
            check_vma=True,
        )(params["blocks"], h)
    else:
        # pipeline (optionally + sp): stage-stacked blocks over pp
        m = num_microbatches or max(1, 2 * pp)
        assert b % m == 0, f"batch {b} not divisible by microbatches {m}"
        stages = jax.tree.map(
            lambda x: x.reshape((pp, x.shape[0] // pp) + x.shape[1:]),
            params["blocks"],
        )
        h_mb = h.reshape((m, b // m) + h.shape[1:])

        def pp_body(stage_blocks, x_mb):
            # local view keeps the sharded stage axis as size 1 — drop it
            stage_blocks = jax.tree.map(lambda a: a[0], stage_blocks)
            t_loc = x_mb.shape[2]
            if sp > 1:
                off = jax.lax.axis_index("sp") * t_loc
            else:
                off = 0
            ang = jax.lax.dynamic_slice_in_dim(angles_full, off, t_loc)

            def stage_fn(blocks, x_one):
                return _scan_blocks(
                    cfg, blocks, x_one, ang, sp_manual=sp > 1, mesh=mesh
                )

            return pipeline_apply(stage_fn, stage_blocks, x_mb, "pp")

        in_layer_spec = P("pp")  # stage axis sharded; rest auto
        h_mb = jax.shard_map(
            pp_body,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: in_layer_spec, stages),
                P(None, None, "sp", None) if sp > 1 else P(),
            ),
            out_specs=P(None, None, "sp", None) if sp > 1 else P(),
            axis_names={"pp", "sp"},
            check_vma=True,
        )(stages, h_mb)
        h = h_mb.reshape((b,) + h_mb.shape[2:])

    h = rms_norm(h, params["ln_f"], cfg.rms_eps)
    return (h @ params["head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# The stack by position, for the paged serving engine (llm/continuous.py):
# one block function, attention through the caller's cache.
# ---------------------------------------------------------------------------


def rotate(x: jax.Array, ang: jax.Array) -> jax.Array:
    """Rotary embedding, rotate-half within the leading ``2 * ang.shape[-1]``
    dims of each head; the rest of the head passes. x: [..., H, hd];
    ang: [..., rotary_dim / 2] for x's leading dims."""
    dtype, r = x.dtype, 2 * ang.shape[-1]
    part = x if r == x.shape[-1] else x[..., :r]
    x1, x2 = jnp.split(part.astype(jnp.float32), 2, axis=-1)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
    ).astype(dtype)
    if r == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., r:]], -1)


def embed(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    """The embedding's rows of ``tokens``, times ``embedding_multiplier``."""
    h = params["embed"][tokens].astype(cfg.dtype)
    return h * cfg.embedding_multiplier


def head_logits(cfg: ModelConfig, params, h: jax.Array) -> jax.Array:
    """Final norm and the head: float32 logits over the vocabulary, times
    ``lm_head_multiplier``. A model with ``tie_embeddings`` has no
    ``head``: the embedding is read the other way."""
    h = rms_norm(h, params["ln_f"], cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"])
    else:
        logits = h @ params["head"]
    return logits.astype(jnp.float32) * cfg.lm_head_multiplier


def _tap_sum(taps, earlier, s):
    """A causal depthwise convolution as shifted products, in float32:
    ``taps`` one row a tap, the oldest first; ``earlier`` the columns of
    ``s`` before each token, the oldest first."""
    return sum(
        tap.astype(jnp.float32) * col.astype(jnp.float32)
        for tap, col in zip(taps, (*earlier, s))
    )


def gated_conv(cfg: ModelConfig, p, x, shift):
    """The operator of a ``conv`` layer: ``[B, C, z] = x W_in``; ``s = B *
    z``; a causal depthwise convolution of ``s`` over the last
    ``conv_kernel`` tokens (``p["conv"]``: one row a tap, the oldest
    first); ``(C * that) W_out``. ``shift(s)`` is the caller's: it gives
    the ``conv_kernel - 1`` columns of ``s`` before each token (oldest
    first, each shaped like ``s``; zeros before the sequence), keeps the
    last of them where the caller keeps a sequence's state, and returns
    them with the caller's cache."""
    gate_in, gate_out, z = jnp.split(x @ p["w_in"], 3, axis=-1)
    s = gate_in * z
    earlier, cache = shift(s)
    mixed = _tap_sum(p["conv"], earlier, s)
    return (gate_out * mixed.astype(cfg.dtype)) @ p["w_out"], cache


_EXACT = jax.lax.Precision.HIGHEST  # float32 products that stay float32 on a TPU


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` ``[..., C, C]``,
    C a power of two: forward substitution by blocks, as matrix products.
    The inverse of a diagonal block of size 2b is ``[[X, 0], [-Y L X,
    Y]]`` of its two halves' inverses X, Y and its lower-left block L; with
    M the block-diagonal matrix of all the halves' inverses and ``a_low``
    all the blocks L in their places that is ``M - M a_low M``, so log2 C
    rounds of two products of whole C x C matrices give the inverse (a
    Neumann series would sum powers of ``a`` that cancel, a substitution
    row by row is C sequential steps, and blocks of 2 x 2 or 4 x 4 as
    arrays of their own take a tile of the chip each)."""
    c = a.shape[-1]
    if c & (c - 1):
        raise ValueError(f"a block of the scan holds a power of two, not {c}")
    at = jnp.arange(c)

    def low(b):
        """``a``'s lower-left blocks of the diagonal blocks of size 2b:
        rows of a block's second half, columns of its first."""
        rows, cols = at[:, None], at[None, :]
        return jnp.where(
            (rows // (2 * b) == cols // (2 * b)) & (rows // b > cols // b),
            a, 0.0,
        )

    inv = jnp.eye(c, dtype=a.dtype) - low(1)  # blocks of 2: X = Y = 1
    b = 2
    while b < c:
        inv = inv - jnp.einsum(
            "...ij,...jk,...kl->...il", inv, low(b), inv, precision=_EXACT
        )
        b *= 2
    return inv


def delta_step(state, q, k, v, g, beta=None):
    """The gated delta rule moved on by one token, every head of every
    sequence alike, in float32: ``S' = exp(g) S``; ``u = beta (v - S'^T
    k)``; ``S = S' + k u^T``; ``o = S^T q``. state: [..., dk, dv]; q, k:
    [..., dk]; v: [..., dv]; g, beta: [...]. Returns (o [..., dv], S).
    With ``beta`` None the transition is diagonal, with no correction by
    what ``S'`` already holds (Mamba-2's: ``u = v``). Products and sums
    over one axis, no matrix unit: nothing is rounded."""
    state = state * jnp.exp(g)[..., None, None]
    if beta is not None:
        v = beta[..., None] * (v - jnp.sum(k[..., None] * state, -2))
    state = state + k[..., None] * v[..., None, :]
    return jnp.sum(q[..., None] * state, -2), state


def delta_scan(state, q, k, v, g, beta=None, block: int = 0):
    """The same recurrence over T tokens of one sequence as a scan over
    blocks of ``block`` tokens (0 = ``DELTA_BLOCK``; a power of two; the
    delta rule's chunked form, Yang et al. 2024, with the gate's decay):
    inside a block products of block-sized matrices, from block to block the
    carried ``S``. state: [H, dk, dv] float32, the state before the first
    token; q, k: [T, H, dk]; v: [T, H, dv]; g, beta: [T, H]; all float32.
    Returns (o [T, H, dv], the state after the last token). A token with g
    = 0 and beta = 0 changes nothing, which is how the caller ends the
    sequence before T and how a ragged last block is filled here.

    With ``c_t`` the sum of g over the block's tokens up to t and ``D[t,
    i] = exp(c_t - c_i)`` for i <= t: ``u`` solves ``(I + A) U = beta (V -
    exp(c) K S_0)``, ``A[t, i] = beta_t D[t, i] k_t.k_i`` below the
    diagonal; ``O = exp(c) Q S_0 + ((Q K^T) * D) U``; ``S_end = exp(c_C)
    S_0 + (K * D[C, :])^T U``. What does not hold ``S_0`` is computed for
    all blocks at once. With ``beta`` None the transition is diagonal
    (``delta_step``'s), ``U = V`` and there is nothing to solve: Mamba-2's
    state-space dual form (Dao and Gu 2024), where q, k and v are C, B and
    dt x and a token with g = 0 and v = 0 changes nothing."""
    block = block or DELTA_BLOCK
    t, heads = g.shape
    n = -(-t // block)
    pad = n * block - t

    def blocks(x):  # [T, H, ...] -> [n, H, block, ...]
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(n, block, *x.shape[1:]), 1, 2)

    q, k, v, g = map(blocks, (q, k, v, g))
    c = jnp.cumsum(g, -1)  # [n, H, C]
    at = jnp.arange(block)
    upto = at[:, None] >= at[None, :]
    # exp of a masked difference: above the diagonal it would overflow
    decay = jnp.exp(jnp.where(upto, c[..., :, None] - c[..., None, :], -jnp.inf))
    since = jnp.exp(c)  # the decay from the block's start to each token
    if beta is None:
        u_free, w = v, None
    else:
        beta = blocks(beta)
        kk = jnp.einsum("nhtd,nhid->nhti", k, k, precision=_EXACT)
        a = jnp.where(at[:, None] > at[None, :], beta[..., None] * decay * kk, 0.0)
        solve = _unit_lower_inverse(a)  # [n, H, C, C]
        u_free = jnp.einsum(
            "nhti,nhid->nhtd", solve, beta[..., None] * v, precision=_EXACT
        )
        w = jnp.einsum(
            "nhti,nhid->nhtd", solve, (beta * since)[..., None] * k,
            precision=_EXACT,
        )
    qk = decay * jnp.einsum("nhtd,nhid->nhti", q, k, precision=_EXACT)
    q_in = since[..., None] * q
    end = since[..., -1]  # [n, H]
    k_out = jnp.exp(c[..., -1:] - c)[..., None] * k

    def one(state, xs):
        u_free, w, qk, q_in, k_out, end = xs
        u = u_free
        if w is not None:
            u = u - jnp.einsum("htk,hkv->htv", w, state, precision=_EXACT)
        o = jnp.einsum("htk,hkv->htv", q_in, state, precision=_EXACT) + (
            jnp.einsum("hti,hiv->htv", qk, u, precision=_EXACT)
        )
        state = end[:, None, None] * state + jnp.einsum(
            "htk,htv->hkv", k_out, u, precision=_EXACT
        )
        return state, o

    state, o = jax.lax.scan(one, state, (u_free, w, qk, q_in, k_out, end))
    return jnp.moveaxis(o, 1, 2).reshape(n * block, heads, -1)[:t], state


def gated_delta(cfg: ModelConfig, p, x, shift, recur):
    """The operator of a ``delta`` layer, the gated delta rule (Yang,
    Kautz, Hatamizadeh 2024): ``[q, k, v] = x W_qkv``, each channel through
    a causal depthwise convolution over the last ``conv_kernel`` tokens
    (``p["conv"]``, the oldest tap first) and SiLU; per head q and k to unit
    length, q times ``dk^-1/2``; ``beta = sigmoid(x W_b)`` (times 2 with
    ``delta_neg_eigval``); ``g = -exp(A_log) softplus(x W_a + dt_bias)``;
    the recurrence of ``delta_step`` over the caller's state; the output
    RMS-normed over a head's dims (one scale for all heads), gated by
    ``silu(x W_g)``, through ``W_o``. ``shift(s)`` is ``gated_conv``'s:
    the columns of ``s`` before each token, and the caller's cache;
    ``recur(q, k, v, g, beta, cache)`` runs the recurrence in float32 from
    where the caller keeps a sequence's ``S`` and returns (o [..., H, dv],
    the cache)."""
    lead = x.shape[:-1]
    heads, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim
    s = x @ p["w_qkv"]
    earlier, cache = shift(s)
    mixed = jax.nn.silu(_tap_sum(p["conv"], earlier, s))
    q, k, v = jnp.split(mixed, [heads * dk, 2 * heads * dk], axis=-1)
    q = q.reshape(*lead, heads, dk)
    k = k.reshape(*lead, heads, dk)

    def unit(y):
        return y / (jnp.sqrt(jnp.sum(y * y, -1, keepdims=True)) + 1e-6)

    beta = jax.nn.sigmoid((x @ p["w_b"]).astype(jnp.float32))
    if cfg.delta_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        (x @ p["w_a"]).astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )
    o, cache = recur(
        unit(q) * dk**-0.5, unit(k), v.reshape(*lead, heads, dv), g, beta,
        cache,
    )
    o = rms_norm(o, p["o_norm"].astype(jnp.float32), cfg.rms_eps)
    gate = jax.nn.silu(x @ p["w_g"])
    return (o.reshape(*lead, -1).astype(cfg.dtype) * gate) @ p["wo"], cache


def mamba2(cfg: ModelConfig, p, x, shift, recur):
    """The Mamba-2 mixer of a ``parallel`` layer (Dao and Gu 2024, as
    Falcon-H1 runs it): ``[z, x, B, C, dt] = (x W_in) * mu`` (``ssm_in``,
    ``mu`` constant on each of the five segments, ``ssm_multipliers``);
    ``x``, ``B``, ``C`` through a causal depthwise convolution over the last
    ``conv_kernel`` tokens with a bias (``ssm_conv``, the oldest tap first;
    ``ssm_conv_bias``) and SiLU; ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` a head; head ``i`` reads group ``i // (heads / groups)``
    of B and C; per head, in float32, ``S_t = exp(dt_t A) S_{t-1} + B_t
    (dt_t x_t)^T`` and ``y_t = S_t^T C_t + D x_t``; then ``y * silu(z)``
    RMS-normed over each group of ``ssm_inner / groups`` channels with one
    scale of ``ssm_inner`` (the gate before the norm), through ``ssm_out``.
    ``shift(s)`` is ``gated_conv``'s, over x, B and C as the projection
    gives them (in the served type); ``recur(q, k, v, g, beta, cache)`` is
    ``gated_delta``'s with ``q = C``, ``k = B``, ``v = dt x``, ``g = dt A``
    and ``beta`` None: the diagonal transition of ``delta_step``."""
    lead, f32 = x.shape[:-1], jnp.float32
    heads, size, n, groups = (
        cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    )
    inner = cfg.ssm_inner
    proj = x @ p["ssm_in"]
    if cfg.ssm_multipliers:
        widths = (inner, inner, groups * n, groups * n, heads)
        proj = proj * jnp.concatenate([
            jnp.full((w,), m, proj.dtype)
            for w, m in zip(widths, cfg.ssm_multipliers)
        ])
    z, xbc, dt = jnp.split(proj, [inner, inner + cfg.ssm_width], axis=-1)
    earlier, cache = shift(xbc)
    xbc = jax.nn.silu(
        _tap_sum(p["ssm_conv"], earlier, xbc) + p["ssm_conv_bias"].astype(f32)
    )
    xs, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    xs = xs.reshape(*lead, heads, size)

    def by_head(y):  # [..., G * N] -> [..., H, N]: head i reads group i // (H / G)
        y = y.reshape(*lead, groups, 1, n)
        return jnp.broadcast_to(
            y, (*lead, groups, heads // groups, n)
        ).reshape(*lead, heads, n)

    dt = jax.nn.softplus(dt.astype(f32) + p["ssm_dt_bias"].astype(f32))
    a = -jnp.exp(p["ssm_a_log"].astype(f32))
    y, cache = recur(by_head(c), by_head(b), dt[..., None] * xs, dt * a, None, cache)
    y = y + p["ssm_d"].astype(f32)[:, None] * xs
    y = gated_group_norm(cfg, y.reshape(*lead, inner), z, p["ssm_norm"])
    return y.astype(cfg.dtype) @ p["ssm_out"], cache


def gated_group_norm(cfg: ModelConfig, y, z, scale):
    """Mamba-2's gated norm with the gate first (Falcon-H1's
    ``mamba_norm_before_gate`` false): ``y * silu(z)``, RMS-normed over
    each of the ``ssm_groups`` groups of channels, times one ``scale`` of
    all of them; float32."""
    f32, groups = jnp.float32, cfg.ssm_groups
    y = y * jax.nn.silu(z.astype(f32))
    y = rms_norm(
        y.reshape(*y.shape[:-1], groups, -1),
        scale.astype(f32).reshape(groups, -1), cfg.rms_eps,
    )
    return y.reshape(*y.shape[:-2], -1)


def decoder_block(cfg: ModelConfig, run: LayerRun, p, h, ang, mix,
                  live=None, layer=None, experts_kernel=None):
    """One layer of the kind ``run`` names: norm, the token mixer through
    the caller's cache, feed-forward; with ``post_norm`` the two norms
    come after the operator and after the feed-forward, inside the
    residual (``x + norm(op(x))``), and nothing is normed before. h: [...,
    D]; ``ang``: the rotary angles [..., rotary_dim / 2] of h's tokens at
    the kind's rope base, None where the kind does not rotate.
    An attention layer: Q K V, rotary, and ``mix(q, k, v, sink)``, which
    gets [..., heads, size] arrays (``sink``: float32[H] or None), writes k
    and v where the caller keeps them and returns (float32 [..., H *
    v_head_dim], the caller's cache). A convolution layer: ``gated_conv``
    with ``mix`` as its ``shift``; a delta layer: ``gated_delta`` with
    ``mix`` as its (``shift``, ``recur``). A parallel layer (Falcon-H1):
    ``mix`` is (the attention's ``mix``, ``shift(s, cache)``, ``recur``),
    and the operator is ``attn(x * attention_in_multiplier) *
    attention_out_multiplier + mamba2(x * ssm_in_multiplier) *
    ssm_out_multiplier`` over the one normed ``x``, k times
    ``key_multiplier`` before the rotary. Returns (h, that cache,
    int32[2]: token-expert pairs this holder computed and held experts
    hit; zeros in a dense layer). ``live``: bool over the leading dims,
    tokens whose choice of expert counts. ``layer``: where ``p["moe"]``
    holds the experts of a whole run of layers, this layer's place among
    them; ``experts_kernel``: ``moe.experts_apply``'s ``kernel``."""
    lead, kind = h.shape[:-1], run.attn
    x = h if cfg.post_norm else rms_norm(h, p["ln1"], cfg.rms_eps)
    if kind.name == "conv":
        op, cache = gated_conv(cfg, p, x, mix)
    elif kind.name == "delta":
        op, cache = gated_delta(cfg, p, x, *mix)
    else:
        attend = mix[0] if kind.state else mix
        xa = x * cfg.attention_in_multiplier

        def project(w, heads, size, norm=None):
            """x W as heads; q and k normed over the whole row before the
            cut into heads, or over each head after it."""
            y = xa @ p[w]
            norm = norm if cfg.qk_norm else None
            if norm and cfg.qk_norm_whole:
                y = rms_norm(y, p[norm], cfg.rms_eps)
            y = y.reshape(*lead, heads, size)
            if norm and not cfg.qk_norm_whole:
                y = rms_norm(y, p[norm], cfg.rms_eps)
            return y

        q = project("wq", cfg.n_heads, cfg.head_dim, "q_norm")
        k = project("wk", kind.kv_heads, cfg.head_dim, "k_norm")
        v = project("wv", kind.kv_heads, cfg.v_head_dim)
        k = k * cfg.key_multiplier
        if cfg.value_scale != 1.0:
            v = v * cfg.value_scale
        if ang is not None:
            q, k = rotate(q, ang), rotate(k, ang)
        attn, cache = attend(q, k, v, p.get("sink"))
        op = attn.astype(cfg.dtype) @ p["wo"]
        if kind.state == "ssm":
            _, shift, recur = mix
            ssm, cache = mamba2(
                cfg, p, x * cfg.ssm_in_multiplier, functools.partial(shift, cache=cache), recur
            )
            op = (op * cfg.attention_out_multiplier
                  + ssm * cfg.ssm_out_multiplier)
    if cfg.post_norm:
        op = rms_norm(op, p["ln1"], cfg.rms_eps)
    h = h + op
    x2 = h if cfg.post_norm else rms_norm(h, p["ln2"], cfg.rms_eps)
    if run.experts:
        y, pairs, hit = moe_mod.experts_apply(
            p["moe"], x2.reshape(-1, cfg.d_model),
            top_k=cfg.experts_per_token, held=cfg.experts_held,
            live=None if live is None else live.reshape(-1),
            norm_eps=cfg.router_norm_eps, scale=cfg.routed_scaling,
            layer=layer, kernel=experts_kernel,
        )
        y = y.reshape(h.shape)
    else:
        y = swiglu(
            x2, p["w_gate"], p["w_up"], p["w_down"], *cfg.mlp_multipliers
        )
    if cfg.post_norm:
        y = rms_norm(y, p["ln2"], cfg.rms_eps)
    counts = jnp.stack([pairs, hit]) if run.experts else jnp.zeros((2,), jnp.int32)
    return h + y, cache, counts


def run_stack(cfg: ModelConfig, blocks, h, positions, cache, attend,
              live=None, shift=None, recur=None, experts_kernel=None):
    """Every layer in the pattern's order, each run of one kind a
    ``lax.scan`` over the run's stacked weights: all of them but an
    expert run's ``moe.EXPERT_WEIGHTS``, which the scan's body closes over
    whole and ``experts_apply`` reads in place by the layer's index in the
    run (as the scan's ``xs`` a layer's ``[held, D, F]`` was copied out of
    the stack every step, before the grouped matmul read it).
    ``positions``: int32 of h's leading dims. ``attend(kind, layer, q, k,
    v, sink, cache) -> (attention, cache)``, ``layer`` counting within the
    kind's class of KV page; for a layer that keeps state by slot
    ``shift(state, layer, s, cache) -> (the columns before each token,
    cache)`` and, for ``delta`` and ``ssm``, ``recur(state, layer, q, k, v,
    g, beta, cache) -> (the recurrence's output, cache)``, ``state`` being
    the kind's ``AttnKind.state`` and ``layer`` counting within that state
    by slot; ``experts_kernel``: ``moe.experts_apply``'s ``kernel``.
    Returns (h, cache, the blocks' int32[2] counts summed)."""
    cfg.require_blocks_by_run(blocks)
    counts = jnp.zeros((2,), jnp.int32)
    for run in cfg.layer_runs():
        stack = blocks if run.key is None else blocks[run.key]
        experts = {}
        if run.experts:
            experts = {k: stack["moe"][k] for k in moe_mod.EXPERT_WEIGHTS}
            stack = {**stack, "moe": {
                k: v for k, v in stack["moe"].items() if k not in experts
            }}
        ang = None
        if run.attn.name not in STATE_KINDS and run.attn.rope_theta:
            ang = rope_freqs(
                cfg.rotary_dim, cfg.max_seq_len, run.attn.rope_theta
            )[positions]

        def body(carry, p, run=run, ang=ang, experts=experts):
            h, cache, layer, counts = carry
            kind = run.attn
            # the layer's row in its state by slot
            in_state = layer - run.cache_start + run.state_start

            def attend_mix(q, k, v, sink):
                return attend(kind, layer, q, k, v, sink, cache)

            def shift_mix(s, cache=cache):
                return shift(kind.state, in_state, s, cache)

            def recur_mix(*args):
                return recur(kind.state, in_state, *args)

            if kind.name == "conv":
                mix = shift_mix
            elif kind.name == "delta":
                mix = (shift_mix, recur_mix)
            elif kind.state:
                mix = (attend_mix, shift_mix, recur_mix)
            else:
                mix = attend_mix
            in_run = None
            if run.experts:
                # `layer` began at the run's first layer in its class
                p = {**p, "moe": {**p["moe"], **experts}}
                in_run = layer - run.cache_start
            h, cache, c = decoder_block(
                cfg, run, p, h, ang, mix, live, in_run, experts_kernel
            )
            return (h, cache, layer + 1, counts + c), None

        (h, cache, _, counts), _ = jax.lax.scan(
            body, (h, cache, jnp.int32(run.cache_start), counts), stack
        )
    return h, cache, counts


def loss_fn(params, tokens, cfg: ModelConfig, mesh=None, *, num_microbatches=0):
    """Causal LM loss: predict tokens[1:] from tokens[:-1]."""
    logits = forward(
        params, tokens[:, :-1], cfg, mesh, num_microbatches=num_microbatches
    )
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def make_train_step(cfg: ModelConfig, optimizer, mesh=None, *, num_microbatches=0):
    """Returns jittable (params, opt_state, tokens) -> (params, opt_state, loss)."""
    cfg.require_uniform_dense("make_train_step")
    configure_compile_cache()
    shardings = None
    if mesh is not None:
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            param_specs(cfg, mesh.shape.get("pp", 1)),
        )

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, cfg, mesh, num_microbatches=num_microbatches
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        if shardings is not None:
            # Parameters, and the optimizer's parameter-shaped state, leave
            # the step sharded as they entered. Left open, the TPU compiler
            # returns the norm scales and their moments split over tp under
            # pp·tp: the next call recompiles (or, compiled ahead of time,
            # refuses its own output) and donation finds no buffer to reuse.
            pin = jax.lax.with_sharding_constraint
            params = pin(params, shardings)
            opt_state = optax.tree_map_params(
                optimizer, pin, opt_state, shardings
            )
        return params, opt_state, loss

    return train_step
