"""Continuous batching engine with a paged KV cache.

The serving tier the reference delegates to vLLM-class engines
(/root/reference/python/ray/llm/_internal/serve/, vllm passthrough) —
rebuilt TPU-first in the JetStream/PagedAttention mold:

- **Paged KV pool**: one device buffer of fixed-size pages
  ``[n_layers, kv_heads, n_pages, page, head_dim]`` (head-major, so one
  DMA of the decode kernel brings a page of every head) shared by every
  sequence; a per-slot block table maps logical positions to pages. All
  shapes static — XLA compiles exactly two programs (per prefill bucket):
  one prefill, one decode step.
- **Continuous batching**: B decode slots; requests admit into free slots
  as others finish (no batch restart), so the decode step always runs at
  the live batch size. Admission backpressures on free pages — the pool,
  not the batch, is the capacity.
- **Decode step**: one token for ALL active slots per jit call; the KV
  write is a per-slot scatter into (page, offset). On a TPU, attention
  over the ``full`` class of page is the Pallas kernel of
  ``ops/paged_attention.py``, which reads the pages that hold live
  positions out of the pool where it lies; where there is no TPU, and for
  a windowed layer's ring, attention gathers each slot's table into a
  contiguous view (gathers + one big einsum, no dynamic shapes).
- **State by slot**: a layer that is no attention keeps no K and V but a
  state of fixed size for each sequence, indexed by slot, beside the pools
  (``PagedKVPool.state``), with no pages to reserve or to wait for: a few
  columns of its own input (a gated short convolution), or such columns
  and a float32 matrix a head (the gated delta rule, a linear attention:
  one token a step in ``decode_step``, a scan over blocks of tokens in the
  two prefill programs, ``models/transformer.py`` ``delta_scan``). A
  parallel layer (Falcon-H1: a Mamba-2 mixer beside the attention) keeps
  both: its attention's pages of the ``full`` class and the mixer's
  columns and matrix a head by slot, written by the same programs.

Reference files for parity intent: vllm paged attention + continuous
batching scheduler; JetStream's slot/page design is the public TPU
pattern this follows.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import transformer as tfm
from ray_tpu.util import tracing
from ray_tpu.util.compile_cache import configure_compile_cache

from .engine import ByteTokenizer, GenerationConfig


@dataclass
class _Slot:
    active: bool = False
    req_id: int = -1
    pos: int = 0  # next position to write
    max_pos: int = 0  # hard stop (prompt + max_new)
    pages: Dict[str, List[int]] = field(default_factory=dict)  # by class
    out: List[int] = field(default_factory=list)
    eos: Optional[int] = None


@dataclass
class _Request:
    """A request from ``submit()``/``adopt_pages()`` to its end. ``span``
    is its ``engine.request`` span, open all that time; the times are
    ``perf_counter`` stamps and the lock counters are summed over every
    acquisition of the engine lock in ``stream_rid``. ``first_step`` is
    the count of the step that gave it its slot: that step made its second
    token and step ``first_step + k - 1`` its token ``k``; the first came
    out of its prefill at ``t_first``. The pickup lags are how long its
    tokens, once on the host, waited for ``stream_rid``'s consumer."""

    req_id: int
    prompt: List[int]
    gen: GenerationConfig
    span: Any = None
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    lock_wait_s: float = 0.0
    lock_wait_max_s: float = 0.0
    lock_acquires: int = 0
    first_step: int = 0
    pickup_lag_s: float = 0.0
    pickup_lag_max_s: float = 0.0


class _PageClass:
    """The free list of one class of page, the one the layers of attention
    kind ``kind`` write. Page 0 is the class's SCRATCH page: inactive
    decode slots are redirected there so their no-op writes can never
    collide with a live slot's page in the same scatter (duplicate-index
    order is unspecified)."""

    def __init__(self, kind: tfm.AttnKind, n_pages: int):
        self.name = kind.name
        self.window = kind.window  # 0: a sequence's pages grow with it
        self.n_pages = n_pages
        self._free = list(range(1, n_pages))
        self._free_set = set(self._free)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def usable_pages(self) -> int:
        return self.n_pages - 1  # minus the scratch page

    def alloc(self, n: int) -> Optional[List[int]]:
        if len(self._free) < n:
            return None
        out = self._free[:n]
        del self._free[:n]
        self._free_set.difference_update(out)
        return out

    def free(self, pages: List[int]) -> None:
        """Return pages to the free-list. Raises on a double free (a
        page id already free, the scratch page, out-of-range, or a
        duplicate within ``pages``): silently re-adding a freed page
        would let ``alloc`` hand the same page to two slots and their
        KV scatters would corrupt each other."""
        seen = set()
        for p in pages:
            if p in seen:
                raise ValueError(
                    f"double free: page {p} appears twice in free({pages})"
                )
            if not 0 < p < self.n_pages:
                raise ValueError(
                    f"free of invalid {self.name} page {p} "
                    f"(scratch page 0 / out of range, n_pages={self.n_pages})"
                )
            if p in self._free_set:
                raise ValueError(
                    f"double free: {self.name} page {p} is already on the "
                    "free-list (one page allocated to two slots corrupts "
                    "both slots' KV)"
                )
            seen.add(p)
        self._free.extend(pages)
        self._free_set.update(pages)


LANES = 128  # a TPU vector register's lanes: the tile of an array's last dim
# what one prefill program's [H, T, T] float32 scores may take: the longest
# prompt a single program runs (2,048 tokens at 64 heads, 2,896 at 32)
PREFILL_SCORES_BYTES = 2**30
# what one trip's float32 scores may take where a chunk of a prompt walks its
# slot's pages (``_table_attention``). Timed alone on a v5e at 30 and at 64
# heads: 32 to 64 MiB read alike, 12 MiB a third to twice slower (more
# trips), 128 MiB two to three times (the compiler no longer keeps a trip's
# scores in the chip's fast memory)
ATTN_BLOCK_BYTES = 32 * 2**20
# steps whose end the engine remembers (``_step_done``): a consumer further
# behind than that finds its token's stamp overwritten and skips its lag.
# Eight minutes of steps at 66 a second
STEP_STAMPS = 1 << 15


# the slot's axis in each array of ``PagedKVPool.state``, and the array
# that holds a kind's columns before its short convolution
SLOT_AXIS = {"conv": 2, "delta_taps": 2, "delta_s": 1, "ssm_taps": 2, "ssm_s": 1}
TAPS_OF = {"conv": "conv", "delta": "delta_taps", "ssm": "ssm_taps"}
# the array that holds a recurrent kind's float32 matrix a head
MATRIX_OF = {"delta": "delta_s", "ssm": "ssm_s"}


def stored_width(size: int, kernel: bool = False) -> int:
    """Width a key or a value is stored at: a head wider than one tile of
    ``LANES`` takes whole tiles (192 -> 256, zeros behind the head's own
    dims). The chip's compiler gives a pool whose rows are one and a half
    tiles wide another layout inside the step than at its ends and copies
    the whole pool twice a program; rows of whole tiles keep one layout, in
    place. Where the Pallas decode kernel reads the pool (``kernel``: on a
    TPU) a narrower head takes a whole tile too (64 -> 128): the chip lays
    a row of 64 out in 128 lanes whatever it is declared as, and Mosaic
    refuses the page's DMA out of it ("slice shape along dimension 4 must
    be aligned to tiling (128), but is 64")."""
    if size <= LANES and not kernel:
        return size
    return -(-size // LANES) * LANES


class PagedKVPool:
    """Fixed pools of KV pages, one for each class of page the model's
    layers write (``cfg.kv_classes()``), each with a host-side free list.

    - class ``full`` (every dense configuration's only class): a sequence
      holds a page for each ``page`` tokens of its context; ``n_pages``
      sizes it, and it is the capacity admission backpressures on.
    - class ``window``: a windowed layer reads the last ``window`` keys and
      no others, so a sequence holds a fixed ring of
      ``ring_pages = ceil(window / page) + 1`` pages, written at
      ``position mod (ring_pages * page)`` and reused in place however long
      the context grows (the one page over the window is what a prompt's
      padding may overwrite without touching a key still in a window).
      Sized for ``max_batch`` rings.

    ``k`` and ``v`` map a class's name to its array
    ``[layers of the class, KV heads, pages, page, head size]``, head-major
    (the Pallas decode kernel and the gather path both read it without a
    transpose); K and V heads may differ in size, and are stored
    ``k_dim = stored_width(head_dim, kernel)`` and ``v_dim`` wide.

    ``state`` holds what the layers that are no attention keep for a
    sequence, by slot and not by page: ``state["conv"]``
    ``[convolution layers, conv_kernel - 1, max_batch, d_model]``, the last
    columns of each layer's gated input, the oldest first;
    ``state["delta_taps"]`` ``[delta layers, conv_kernel - 1, max_batch,
    delta_width]``, the last columns of q, k and v before their short
    convolution, and ``state["delta_s"]`` ``[delta layers, max_batch,
    heads, key size, value size]`` in float32, the delta rule's matrix a
    head; ``state["ssm_taps"]`` ``[parallel layers, conv_kernel - 1,
    max_batch, ssm_width]`` and ``state["ssm_s"]`` ``[parallel layers,
    max_batch, heads, state size, head size]`` in float32, the same of a
    parallel layer's Mamba-2 mixer (``SLOT_AXIS``: which axis of each is
    the slot's). It has no free
    list: a slot's state is its occupant's from the prefill that wrote it,
    nothing is reserved and nothing can run short."""

    def __init__(self, cfg: tfm.ModelConfig, n_pages: int, page: int,
                 max_batch: int = 0, kernel: bool = False):
        self.page = page
        self.k_dim = stored_width(cfg.head_dim, kernel)
        self.v_dim = stored_width(cfg.v_head_dim, kernel)
        self.ring_pages = 0
        self.classes: Dict[str, _PageClass] = {}
        self.k: Dict[str, jax.Array] = {}
        self.v: Dict[str, jax.Array] = {}
        for name, (layers, kind) in cfg.kv_classes().items():
            n = n_pages
            if kind.window:
                self.ring_pages = -(-kind.window // page) + 1
                n = max_batch * self.ring_pages + 1
            self.classes[name] = _PageClass(kind, n)
            shape = (layers, kind.kv_heads, n, page)
            self.k[name] = jnp.zeros(shape + (self.k_dim,), cfg.dtype)
            self.v[name] = jnp.zeros(shape + (self.v_dim,), cfg.dtype)
        self.state: Dict[str, jax.Array] = {}
        kinds, taps = cfg.state_kinds(), cfg.conv_kernel - 1
        if "conv" in kinds:
            self.state["conv"] = jnp.zeros(
                (kinds["conv"], taps, max_batch, cfg.d_model), cfg.dtype
            )
        if "delta" in kinds:
            self.state["delta_taps"] = jnp.zeros(
                (kinds["delta"], taps, max_batch, cfg.delta_width), cfg.dtype
            )
            self.state["delta_s"] = jnp.zeros(
                (kinds["delta"], max_batch, cfg.delta_heads,
                 cfg.delta_key_dim, cfg.delta_value_dim), jnp.float32,
            )
        if "ssm" in kinds:
            self.state["ssm_taps"] = jnp.zeros(
                (kinds["ssm"], taps, max_batch, cfg.ssm_width), cfg.dtype
            )
            self.state["ssm_s"] = jnp.zeros(
                (kinds["ssm"], max_batch, cfg.ssm_heads, cfg.ssm_state,
                 cfg.ssm_head_dim), jnp.float32,
            )
        # bytes of state by slot one sequence holds, as declared, by array
        self.slot_bytes = {
            name: a.nbytes // a.shape[SLOT_AXIS[name]]
            for name, a in self.state.items()
        }
        self.state_bytes_per_slot = sum(self.slot_bytes.values())

    @property
    def n_pages(self) -> int:
        return sum(c.n_pages for c in self.classes.values())

    @property
    def free_pages(self) -> int:
        return sum(c.free_pages for c in self.classes.values())

    @property
    def usable_pages(self) -> int:
        return sum(c.usable_pages for c in self.classes.values())

    def need(self, tokens: int) -> Dict[str, int]:
        """Pages of each class a sequence of ``tokens`` tokens holds."""
        grows = -(-max(tokens, 1) // self.page)
        return {
            name: self.ring_pages if c.window else grows
            for name, c in self.classes.items()
        }

    def short(self, need: Dict[str, int]) -> Optional[str]:
        """The class that cannot give ``need`` now, if any."""
        for name, n in need.items():
            if self.classes[name].free_pages < n:
                return name
        return None

    def alloc(self, need: Dict[str, int]) -> Optional[Dict[str, List[int]]]:
        """Pages of every class, or none of any."""
        if self.short(need) is not None:
            return None
        return {name: self.classes[name].alloc(n) for name, n in need.items()}

    def free(self, pages: Dict[str, List[int]]) -> None:
        for name, ids in pages.items():
            self.classes[name].free(ids)


# the operands every program that writes a sequence's memory donates
_POOL = ("pool_k", "pool_v", "state")


class KVPoolLost(RuntimeError):
    """A program that writes the KV pool failed after the pool had been
    donated to it: the pages of every live request are gone with it, and
    the engine cannot serve until it is built again."""


@functools.partial(jax.jit, donate_argnames=("pool_k", "pool_v"))
def _scatter_pages(pool_k, pool_v, pages, k, v):
    """Write whole pages that were computed elsewhere (a prefix-cache hit,
    a prefill worker's handoff) into the ``full`` class of the pool. k, v:
    ``[L, KH, len(pages), page, hd]``."""
    pk, pv = pool_k["full"], pool_v["full"]
    return (
        {**pool_k, "full": pk.at[:, :, pages].set(k.astype(pk.dtype))},
        {**pool_v, "full": pv.at[:, :, pages].set(v.astype(pv.dtype))},
    )


@jax.jit
def _gather_pages(pool_k, pool_v, pages):
    """The given pages of the ``full`` class out of the pool, K and V:
    ``[L, KH, len(pages), page, size]`` each, new buffers. One program
    for each count of pages: indexed eagerly, every count compiled eight
    small programs of its own (the index's bounds and wrap-around beside
    the gather), over half of the programs a replica's warm-up lowered."""
    return pool_k["full"][:, :, pages], pool_v["full"][:, :, pages]


def _named(patterns) -> str:
    return ", ".join(f'"{p}"' for p in patterns)


def _locked(method):
    """Run an engine method under the engine's lock."""

    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return method(self, *args, **kwargs)

    return wrapper


def _softmax(scores, sink=None):
    """Softmax over the last axis. ``sink`` (broadcast against the other
    axes) is one more column that takes mass and gives no value."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    m = jnp.maximum(jnp.max(scores, axis=-1), sink)
    e = jnp.exp(scores - m[..., None])
    return e / (jnp.sum(e, axis=-1) + jnp.exp(sink - m))[..., None]


def _window_attention(q, k, v, hist_k, hist_v, q_from, window, sink):
    """Windowed causal attention of a block of ``t`` consecutive tokens
    whose first is at position ``q_from``: a query sees the ``window``
    keys that end with its own. The tokens are cut into blocks of
    ``window`` queries, and each block sees itself and the block before
    it, so the scores are ``[t, 2 * window]`` and not ``[t, t]``; what lies
    before the first block is ``hist_k``/``hist_v`` ``[window, KH, size]``,
    the ``window`` positions before ``q_from`` (those below 0 are
    masked). q: [t, H, hd]; k, v: [t, KH, size]; sink: float32[H] or None.
    Returns float32 [t, H * v size]."""
    t, h, hd = q.shape
    kh = k.shape[1]
    g, w = h // kh, window
    nb = -(-t // w)
    pad = nb * w - t

    def blocks(x, before):
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        x = x.reshape(nb, w, *x.shape[1:])
        prev = jnp.concatenate([before[None], x[:-1]], 0)
        return jnp.concatenate([prev, x], 1)  # [nb, 2w, KH, size]

    ks, vs = blocks(k, hist_k), blocks(v, hist_v)
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, w, kh, g, hd)
    q_pos = q_from + jnp.arange(nb * w).reshape(nb, w)
    k_pos = q_pos[:, :1] - w + jnp.arange(2 * w)  # [nb, 2w]
    scores = jnp.einsum(
        "nqkgd,nskd->nkgqs", qb.astype(jnp.float32), ks.astype(jnp.float32)
    ) / jnp.sqrt(hd)
    seen = (
        (k_pos[:, None, :] <= q_pos[:, :, None])
        & (k_pos[:, None, :] > q_pos[:, :, None] - w)
        & (k_pos[:, None, :] >= 0)
    )
    scores = jnp.where(seen[:, None, None], scores, -1e30)
    probs = _softmax(
        scores, None if sink is None else sink.reshape(kh, g)[None, :, :, None]
    )
    out = jnp.einsum("nkgqs,nskd->nqkgd", probs, vs.astype(jnp.float32))
    return out.reshape(nb * w, -1)[:t]


def _attention_block_pages(t: int, heads: int, page: int, table: int) -> int:
    """Pages of a slot's table one trip of ``_table_attention`` takes: as
    many as keep the trip's ``[t, heads, keys]`` float32 scores within
    ``ATTN_BLOCK_BYTES``, at least one and at most the table."""
    return max(1, min(table, ATTN_BLOCK_BYTES // (4 * t * heads * page)))


def _table_attention(q, pool_k, pool_v, layer, table, pos, head_dim):
    """Causal attention of a block of ``t`` queries over the keys a slot
    holds in its pages, walked in blocks of whole pages as far as the keys
    go: trip ``i`` gathers ``block_pages`` entries of ``table`` out of the
    pool's ``layer``, scores them in float32, masks by ``key position <=
    pos`` and folds them into a running maximum, a running sum and a float32
    accumulator (the softmax over all keys, summed block by block and
    divided once at the end). The trip count ``ceil((pos[-1] + 1) /
    block)`` is traced, so one program serves every history, and what the
    table holds past the last query's position is never read. Key 0 is
    seen by every query, so a block wholly masked for a row leaves its
    running values as they were. q: [t, KH, G, size] at the pool's stored
    width, of which ``head_dim`` are the head's own; pool_k, pool_v:
    [layers, KH, pages, page, size]; table: int32[P]; pos: int32[t],
    increasing. Returns float32 [t, KH, G, v size]."""
    t, kh, g, _ = q.shape
    page = pool_k.shape[3]
    block_pages = _attention_block_pages(t, kh * g, page, table.shape[0])
    block = block_pages * page
    trips = -(-table.shape[0] // block_pages)
    # a slice past the table's end would be moved back inside it: unfilled
    # entries name the scratch page, whose positions no query reaches
    table = jnp.pad(table, (0, trips * block_pages - table.shape[0]))
    qf = jnp.transpose(q, (1, 2, 0, 3)).astype(jnp.float32)  # [KH, G, t, size]

    def fold(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, i * block_pages, block_pages)
        # one gather out of the pool as it lies, by (layer, head, page): a
        # slice of the layer first does not depend on the trip, and the
        # compiler would take it out of the loop, a copy of a layer's pages
        at = (layer * kh + jnp.arange(kh)[:, None]) * pool_k.shape[2] + ids
        ks = pool_k.reshape(-1, *pool_k.shape[3:])[at].reshape(kh, block, -1)
        vs = pool_v.reshape(-1, *pool_v.shape[3:])[at].reshape(kh, block, -1)
        scores = jnp.einsum(
            "kgtd,ksd->kgts", qf, ks.astype(jnp.float32)
        ) / jnp.sqrt(head_dim)
        seen = (i * block + jnp.arange(block))[None, :] <= pos[:, None]
        scores = jnp.where(seen[None, None], scores, -1e30)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        keep = jnp.exp(m - m_new)
        acc = acc * keep[..., None] + jnp.einsum(
            "kgts,ksd->kgtd", p, vs.astype(jnp.float32)
        )
        return m_new, l * keep + jnp.sum(p, axis=-1), acc

    m, l, acc = jax.lax.fori_loop(
        0, -(-(pos[-1] + 1) // block), fold,
        (
            jnp.full((kh, g, t), -1e30, jnp.float32),
            jnp.zeros((kh, g, t), jnp.float32),
            jnp.zeros((kh, g, t, pool_v.shape[-1]), jnp.float32),
        ),
    )
    return jnp.transpose(acc / l[..., None], (2, 0, 1, 3))


class ContinuousBatchingEngine:
    """Slot-based continuous batching over the flagship transformer.

    Thread contract: a serve replica runs up to ``max_concurrency``
    requests at once, and each of them drives ``step()`` until its own
    answer is there. Everything that touches slots, queue, pool or the
    device-side slot state therefore runs under one lock; a step taken by
    any request's thread advances every active slot."""

    def __init__(
        self,
        cfg: tfm.ModelConfig,
        params: Optional[Any] = None,
        *,
        max_batch: int = 8,
        page_size: int = 16,
        n_pages: int = 256,
        max_pages_per_seq: Optional[int] = None,
        tokenizer: Optional[Any] = None,
        prefix_cache: Optional[Any] = None,
        model_id: str = "base",
    ):
        if cfg.n_experts > 0:
            raise tfm.UnsupportedModelFeature(
                "`n_experts` is the train step's Switch layer, which drops "
                "tokens over a capacity by their place in the batch; the "
                "paged engine serves dropless experts (`ffn_pattern`, "
                "`n_routed_experts`)"
            )
        self.windowed = "window" in cfg.kv_classes()
        self.stateful = cfg.state_layers > 0
        self.recurrent = any(k in MATRIX_OF for k in cfg.state_kinds())
        if (self.windowed or self.stateful) and prefix_cache is not None:
            raise tfm.UnsupportedModelFeature(
                "the shared prefix cache holds pages of the `full` class "
                "alone; a model with a window class of KV page "
                "(`attn_pattern` \"window\") or with state by slot "
                f"(`attn_pattern` {_named(cfg.state_patterns())}) is served "
                "with prefix_cache=False"
            )
        configure_compile_cache()
        self.cfg = cfg
        self.B = max_batch
        self.page = page_size
        # how decode_step attends over the ``full`` class of page, runs its
        # expert products and moves a float32 state a head on: on a TPU the
        # Pallas kernels (paged attention, Megablox gmm, the state step),
        # elsewhere the XLA gather, lax.ragged_dot and jax.numpy (prefill
        # keeps ragged_dot everywhere: set-up, PERF.md section 6). Read
        # from the platform, set by no caller; CPU tests put "interpret" here
        kernel = "compiled" if jax.default_backend() == "tpu" else None
        self._attn_kernel = self._moe_kernel = self._state_kernel = kernel
        self.pool = PagedKVPool(
            cfg, n_pages, page_size, max_batch, kernel=bool(self._attn_kernel)
        )
        self.max_pages_per_seq = min(
            max_pages_per_seq
            or (min(cfg.max_seq_len, n_pages * page_size) // page_size),
            n_pages - 1,
        )
        # the longest prompt one prefill program takes: its [H, T, T]
        # float32 scores stay within PREFILL_SCORES_BYTES. A longer
        # prompt's rest goes through the history-plus-suffix program in
        # chunks of a quarter of that length, which walks the keys before
        # and in the chunk block by block: its scores are [H, chunk, block]
        # a trip and its time grows with T only. Whole pages both.
        whole = int((PREFILL_SCORES_BYTES / (4 * cfg.n_heads)) ** 0.5)
        self.max_prefill_tokens = max(1, whole // page_size) * page_size
        self.prefill_chunk = (
            max(1, self.max_prefill_tokens // 4 // page_size) * page_size
        )
        self.tokenizer = tokenizer or ByteTokenizer()
        # optional cross-replica prefix/KV cache (serve.prefix_cache):
        # page-aligned prompt prefixes restore from pinned shm views and
        # only the suffix pays prefill compute
        self.prefix_cache = prefix_cache
        self.params = (
            params
            if params is not None
            else tfm.init_params(cfg, jax.random.PRNGKey(0))
        )
        cfg.require_blocks_by_run(self.params["blocks"])
        self._lock = threading.RLock()
        self.slots = [_Slot() for _ in range(self.B)]
        self.queue: deque = deque()
        self.results: Dict[int, List[int]] = {}
        # every request the engine has not ended yet, queued or in a slot
        self._live: Dict[int, _Request] = {}
        self._next_req = 0
        # running totals behind stats(): the operator's view of what the
        # engine.admit / engine.request spans carry one by one
        self.admit_pool_stalls = 0
        self.lock_wait_s = 0.0
        self.lock_acquires = 0
        self.queue_wait_s = 0.0
        # when each step's tokens were on the host (a ``perf_counter`` stamp
        # where ``engine.readback`` ends), by step count in a ring: what
        # ``stream_rid`` measures a token's wait for its consumer from
        self._steps = 0
        self._step_done = [0.0] * STEP_STAMPS
        # disaggregated serving (PR 18): which weights this engine runs,
        # bumped by swap_params; manifests stamp both so a decode engine
        # never grafts KV computed under different weights
        self.model_id = model_id
        self.weights_epoch = 0
        self._swapping = False
        # bounded swap drain (ISSUE 20): when the drain outlives
        # cfg.serve_swap_drain_deadline_s, stuck slots are force-evicted
        # and parked submits get a typed Overloaded instead of hanging
        self._swap_started: Optional[float] = None
        self.swap_force_evicted = 0
        # full-prefill vs page-adoption accounting: the disagg bench's
        # zero-re-prefill gate reads these off the decode replicas
        self.full_prefill_count = 0
        self.adopted_count = 0
        self._prefill_counts = None
        # device-side slot state: each slot's table of pages, by class
        self.block_tables = {
            name: jnp.zeros((self.B, self._table_len(name)), jnp.int32)
            for name in self.pool.classes
        }
        self.positions = jnp.zeros((self.B,), jnp.int32)
        self.cur_tokens = jnp.zeros((self.B,), jnp.int32)
        self.active_mask = jnp.zeros((self.B,), bool)
        # per-slot sampling temperature (0 = greedy) and per-slot seed:
        # each slot's key derives from its request's seed + its own
        # position, so temperature>0 output is per-request deterministic
        # regardless of which other requests are co-resident in the batch
        self.temps = jnp.zeros((self.B,), jnp.float32)
        self.seeds = jnp.zeros((self.B,), jnp.uint32)
        self._build_fns()

    def _table_len(self, name: str) -> int:
        """Entries of a slot's table of pages of that class."""
        if self.pool.classes[name].window:
            return self.pool.ring_pages
        return self.max_pages_per_seq

    def _refuse_windowed(self, what: str) -> None:
        """For the paths that move a sequence as pages of the ``full``
        class: what else a sequence holds would be left behind."""
        kinds = _named(self.cfg.state_patterns())
        for has, field in ((self.windowed, "a window class of KV page"),
                           (self.stateful, "state by slot (`attn_pattern` "
                                           f"{kinds})")):
            if has:
                raise tfm.UnsupportedModelFeature(
                    f"{what} moves pages of the `full` class alone and is "
                    f"not implemented for a model with {field}"
                )

    # ------------------------------------------------------------------
    # jitted programs
    # ------------------------------------------------------------------
    def _build_fns(self) -> None:
        cfg = self.cfg
        page = self.page
        P_max = self.max_pages_per_seq
        S_max = P_max * page
        ring = self.pool.ring_pages * page  # tokens a slot's ring holds
        k_dim, v_dim = self.pool.k_dim, self.pool.v_dim
        taps = cfg.conv_kernel - 1  # columns a short convolution keeps

        def stored(x, width=k_dim):
            """Keys (or values, ``width=v_dim``) at the width the pool
            stores them, or queries to meet them: zeros behind the head's
            own dims."""
            if x.shape[-1] == width:
                return x
            pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
            return jnp.pad(x, pad)

        # The two prefill programs carry through the stack the rows of ONE
        # slot (``[convolution layers, taps, D]``; a delta layer's ``[layers,
        # taps, width]`` and ``[layers, H, dk, dv]``), and put them into the
        # state once, after the last layer: with the whole state in the
        # layers' scan the chip's compiler gave it another layout inside
        # the program and copied it in and out.
        def slot_rows(state, slot):
            """Each array of the state without its slot axis, at ``slot``."""
            return {
                name: jnp.squeeze(jax.lax.dynamic_slice_in_dim(
                    a, slot, 1, SLOT_AXIS[name]), SLOT_AXIS[name])
                for name, a in state.items()
            }

        def put_slot_rows(state, slot, rows):
            return {
                name: jax.lax.dynamic_update_slice_in_dim(
                    a, jnp.expand_dims(rows[name], SLOT_AXIS[name]), slot,
                    SLOT_AXIS[name],
                )
                for name, a in state.items()
            }

        def shift_sequence(state, layer, s, cache, true_len):
            """``run_stack``'s ``shift`` for ONE sequence's block of
            tokens. s: [1, T, D]; ``cache["rows"][...][layer]``: [taps,
            D], the columns before the block's first token. Leaves there
            the ``taps`` columns before position ``true_len`` of the block
            (of a padded block's real tokens, the last): what the padding
            holds is never state."""
            name, t = TAPS_OF[state], s.shape[1]
            rows = cache["rows"][name]
            ext = jnp.concatenate([rows[layer].astype(s.dtype), s[0]], 0)
            earlier = tuple(ext[j : j + t][None] for j in range(taps))
            end = jax.lax.dynamic_slice_in_dim(ext, true_len, taps, 0)
            rows = jax.lax.dynamic_update_index_in_dim(
                rows, end.astype(rows.dtype), layer, 0
            )
            return earlier, {**cache, "rows": {**cache["rows"], name: rows}}

        def scan_sequence(state, layer, q, k, v, g, beta, cache, true_len):
            """``run_stack``'s ``recur`` for ONE sequence's block of
            tokens: the delta rule, or a parallel layer's diagonal
            recurrence (``beta`` None), as a scan over blocks of tokens
            (``tfm.delta_scan``), from ``cache["rows"][<its matrix>][layer]``
            [H, dk, dv], the state before the block's first token, to the
            state after its first ``true_len``, which is left there: at
            and after ``true_len`` the gate is 1 and beta (or v) 0, so the
            padding changes nothing. q, k, v: [1, T, H, size]; g, beta:
            [1, T, H]."""
            name = MATRIX_OF[state]
            rows = cache["rows"][name]
            real = (jnp.arange(g.shape[1]) < true_len)[:, None]
            if beta is None:  # the diagonal recurrence: v = dt x, 0 past the end
                v = jnp.where(real[..., None], v[0], 0.0)
            else:
                v, beta = v[0], jnp.where(real, beta[0], 0.0)
            o, end = tfm.delta_scan(
                rows[layer], q[0], k[0], v, jnp.where(real, g[0], 0.0), beta
            )
            rows = jax.lax.dynamic_update_index_in_dim(rows, end, layer, 0)
            return o[None], {**cache, "rows": {**cache["rows"], name: rows}}

        def write_token(pool, layer, page_ids, offsets, active, x):
            """One token a slot at (page, offset), head-major. x: [B, KH,
            size]; index arrays broadcast to [B, KH]; an inactive slot
            keeps what the scratch page held."""
            hidx = jnp.arange(x.shape[1])[None, :]
            at = (layer, hidx, page_ids[:, None], offsets[:, None])
            return pool.at[at].set(
                jnp.where(active[:, None, None], x.astype(pool.dtype), pool[at])
            )

        def write_pages(pool, layer, page_ids, x):
            """Whole pages of one sequence. x: [T, KH, size] -> [KH, T,
            size] -> [KH, pages, page, size] (a prompt-sized transpose,
            prefill only); scatter indexes broadcast to [KH, pages]."""
            kh = x.shape[1]
            xp = jnp.transpose(x, (1, 0, 2)).reshape(
                kh, -1, page, x.shape[-1]
            )
            hidx = jnp.arange(kh)[:, None]
            return pool.at[layer, hidx, page_ids[None, :]].set(
                xp.astype(pool.dtype)
            )

        def _attention_pages(kind, q, k_pages, v_pages, valid, sink):
            """q: [B,H,hd] one token per slot; k/v_pages head-major
            [KH,B,P,page,size]; valid: bool[B, P*page], the keys each query
            sees. The einsums index the head-major layout directly — no
            materialized transpose."""
            b, kh = q.shape[0], kind.kv_heads
            groups = cfg.n_heads // kh
            s = valid.shape[1]
            ks = k_pages.reshape(kh, b, s, k_dim)
            vs = v_pages.reshape(kh, b, s, v_dim)
            qh = stored(q.reshape(b, kh, groups, cfg.head_dim))
            scores = jnp.einsum(
                "bhgd,hbsd->bhgs",
                qh.astype(jnp.float32),
                ks.astype(jnp.float32),
            ) / jnp.sqrt(cfg.head_dim)
            scores = jnp.where(valid[:, None, None, :], scores, -1e30)
            probs = _softmax(
                scores,
                None if sink is None else sink.reshape(kh, groups)[None],
            )
            attn = jnp.einsum(
                "bhgs,hbsd->bhgd", probs, vs.astype(jnp.float32)
            )
            return attn[..., : cfg.v_head_dim].reshape(b, -1)

        def ring_positions(last):
            """The position each entry of a ring holds once ``last`` is
            written: the latest one at or before it that falls there.
            last: int32[...]; returns int32[..., ring]."""
            at = jnp.arange(ring)
            return last[..., None] - (last[..., None] - at) % ring

        # every program that writes the pool takes it donated and returns
        # it as its second and third results: the output aliases the input,
        # so the scatter is in place and nothing of the pool's size is
        # copied. The state by slot of a model that has any goes the same
        # way, as the last operand and a fourth result
        def results(first, cache):
            out = (first, cache["k"], cache["v"])
            return out + (cache["state"],) if self.stateful else out

        @functools.partial(jax.jit, donate_argnames=_POOL)
        def decode_step(
            params, pool_k, pool_v, tables, positions, tokens, active,
            temps, seeds, state,
        ):
            """One token for every slot. Inactive slots run the same
            math (one trace) but their KV writes are redirected to the
            reserved scratch page 0, so they can never collide with a
            live slot's pages in the scatter. Returns the tokens, int32[6]
            (token-expert pairs computed here and held experts hit, summed
            over the expert layers, live slots only; then of the attention
            layers of the ``full`` class how many ran in the Pallas kernel
            and how many there were, the pages the kernel walked and the
            table entries of those layers, which the gather would have
            read; with state by slot two more: the layers that keep it and
            the rows of it written, one a layer a live slot; with a float32
            matrix of state a head one more, its layers moved on in the
            state kernel; with experts the expert layers run in ``gmm``),
            the pool and the state."""
            b = self.B
            h = tfm.embed(cfg, params, tokens)  # [B, D]
            # positions a slot's query sees, itself among them; none if idle
            lengths = jnp.where(active, positions + 1, 0)
            live_pages = jnp.sum(-(-lengths // page))
            live_slots = jnp.sum(active)

            def attend(kind, layer, q, k, v, sink, cache):
                pool_k, pool_v, walked = cache["k"], cache["v"], cache["walked"]
                name, table = kind.name, tables[kind.name]
                pk, pv = pool_k[name], pool_v[name]
                # a windowed layer's table is the slot's ring of pages
                at = positions % ring if kind.window else positions
                page_ids = jnp.take_along_axis(
                    table, (at // page)[:, None], axis=1
                )[:, 0]  # [B] physical page per slot
                # inactive slots write the reserved scratch page (0): their
                # stale tables may point at pages since reallocated to a
                # LIVE slot, and a duplicate-index scatter could drop its
                # write
                page_ids = jnp.where(active, page_ids, 0)
                offsets = jnp.where(active, at % page, 0)
                pk = write_token(
                    pk, layer, page_ids, offsets, active, stored(k)
                )
                pv = write_token(
                    pv, layer, page_ids, offsets, active, stored(v, v_dim)
                )
                # separate paths by the layer's kind: a ring's mask and
                # sink are another computation, not other parameters of the
                # kernel's. The kernel reads the pool where it lies, after
                # the scatter; the layer is its operand, not a slice
                in_kernel = bool(self._attn_kernel) and not kind.window
                if in_kernel:
                    from ray_tpu.ops.paged_attention import (
                        paged_attention_decode,
                    )

                    qh = q.reshape(b, kind.kv_heads, -1, cfg.head_dim)
                    attn = paged_attention_decode(
                        stored(qh), pk, pv, layer, table, lengths,
                        scale=cfg.head_dim**-0.5,
                        interpret=self._attn_kernel == "interpret",
                    )[..., : cfg.v_head_dim].reshape(b, -1)
                else:
                    if kind.window:
                        held = ring_positions(positions)
                        valid = (held >= 0) & (
                            held > positions[:, None] - kind.window
                        )
                    else:
                        valid = jnp.arange(S_max)[None, :] <= positions[:, None]
                    k_pages = pk[layer][:, table]  # [KH, B, P, page, hd]
                    v_pages = pv[layer][:, table]
                    attn = _attention_pages(
                        kind, q, k_pages, v_pages, valid, sink
                    )
                if not kind.window:
                    walked = walked + jnp.stack([
                        int(in_kernel), 1, in_kernel * live_pages, table.size
                    ]).astype(jnp.int32)
                return attn, {
                    **cache, "k": {**pool_k, name: pk},
                    "v": {**pool_v, name: pv}, "walked": walked,
                }

            def shift(state, layer, s, cache):
                """Every slot's columns before its token, and the slot's
                state moved on by one: an inactive slot keeps what its row
                held, which no live slot reads."""
                name = TAPS_OF[state]
                conv = cache["state"][name]
                held = conv[layer]  # [taps, B, D]
                moved = jnp.concatenate(
                    [held[1:], s[None].astype(held.dtype)], 0
                )
                moved = jnp.where(active[None, :, None], moved, held)
                wrote = jnp.stack([1, live_slots]).astype(jnp.int32)
                conv = jax.lax.dynamic_update_index_in_dim(conv, moved, layer, 0)
                return tuple(held), {
                    **cache, "state": {**cache["state"], name: conv},
                    "stated": cache["stated"] + wrote,
                }

            def recur(state, layer, q, k, v, g, beta, cache):
                """Every slot's ``S`` moved on by its token
                (``tfm.delta_step``); an inactive slot keeps its own. The
                kernel moves the layer's rows on where they lie, each read
                once and written once; the layer is its operand, not a
                slice."""
                name = MATRIX_OF[state]
                every = cache["state"][name]
                if self._state_kernel:
                    from ray_tpu.ops.state_step import state_step_decode

                    o, every = state_step_decode(
                        every, layer, active, q, k, v, g, beta,
                        interpret=self._state_kernel == "interpret",
                    )
                else:
                    held = every[layer]  # [B, H, dk, dv]
                    o, moved = tfm.delta_step(held, q, k, v, g, beta)
                    moved = jnp.where(active[:, None, None, None], moved, held)
                    every = jax.lax.dynamic_update_index_in_dim(
                        every, moved, layer, 0
                    )
                return o, {
                    **cache, "state": {**cache["state"], name: every},
                    "recurred": cache["recurred"] + bool(self._state_kernel),
                }

            # of a model with a float32 matrix of state a head: its layers
            # whose update ran in the kernel
            recurred = (
                {"recurred": jnp.zeros((1,), jnp.int32)} if self.recurrent
                else {}
            )
            h, cache, moe = tfm.run_stack(
                cfg, params["blocks"], h, positions,
                {"k": pool_k, "v": pool_v, "state": state,
                 "walked": jnp.zeros((4,), jnp.int32),
                 "stated": jnp.zeros((2,), jnp.int32), **recurred},
                attend, live=active, shift=shift, recur=recur,
                experts_kernel=self._moe_kernel,
            )
            counts = [moe, cache["walked"]]
            if self.stateful:
                counts.append(cache["stated"])
            if self.recurrent:
                counts.append(cache["recurred"])
            if cfg.n_routed_experts:
                kernel_layers = bool(self._moe_kernel) * sum(
                    r.count for r in cfg.layer_runs() if r.experts
                )
                counts.append(jnp.full((1,), kernel_layers, jnp.int32))
            logits = tfm.head_logits(cfg, params, h)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # per-slot key = fold(request seed, absolute position of the
            # token being produced); prefill samples its first token with
            # fold(seed, prompt_len), decode continues at prompt_len+1…
            # — a slot's stream never depends on co-resident requests
            sampled = jax.vmap(
                lambda sd, pos, lg, tt: jax.random.categorical(
                    jax.random.fold_in(jax.random.PRNGKey(sd), pos + 1),
                    lg / jnp.maximum(tt, 1e-6),
                )
            )(seeds, positions, logits, temps).astype(jnp.int32)
            nxt = jnp.where(temps > 0.0, sampled, greedy)
            return results((nxt, jnp.concatenate(counts)), cache)

        def ring_write(pool, layer, table, first_page, x):
            """The last pages of a block of whole pages into a slot's
            ring: page ``n`` of the sequence lies at entry ``n mod
            ring_pages``. x: [T, KH, size], T a multiple of ``page``, its
            first token on page ``first_page`` of the sequence."""
            pages = min(x.shape[0] // page, self.pool.ring_pages)
            first_page = first_page + x.shape[0] // page - pages
            at = (first_page + jnp.arange(pages)) % self.pool.ring_pages
            return write_pages(
                pool, layer, table[at], x[x.shape[0] - pages * page :]
            )

        def last_logits(params, h, true_len):
            """The head over the one row the host reads, the last real
            token's: float32[vocabulary]. Over every row of a prompt it
            would be [t_pad, vocabulary] (2 GiB of float32 at 2,048 tokens
            and 261,120 ids) for one row read."""
            return tfm.head_logits(cfg, params, h[0, true_len - 1])

        @functools.partial(
            jax.jit, static_argnames=("t_pad",), donate_argnames=_POOL
        )
        def prefill(
            params, pool_k, pool_v, tokens, t_pad, page_ids, state, slot,
            true_len,
        ):
            """Prefill ONE sequence of (padded) length t_pad from its
            first token; write its KV into the given pages and, of a model
            with state by slot, the state of its first ``true_len`` tokens
            (the real ones) into row ``slot``; return (the logits of the
            last real token, the one row the host reads, float32[vocab];
            int32[2] expert counts). tokens: int32[t_pad]; page_ids by
            class: ``full`` int32[t_pad // page], ``window`` the slot's
            ring."""
            pos = jnp.arange(t_pad)
            h = tfm.embed(cfg, params, tokens)[None]  # [1,T,D]

            def shift(state, layer, s, cache):
                return shift_sequence(state, layer, s, cache, true_len)

            def recur(state, layer, q, k, v, g, beta, cache):
                return scan_sequence(
                    state, layer, q, k, v, g, beta, cache, true_len
                )

            def attend(kind, layer, q, k, v, sink, cache):
                pool_k, pool_v = cache["k"], cache["v"]
                name, kh = kind.name, kind.kv_heads
                pk, pv = pool_k[name], pool_v[name]
                if kind.window:
                    # nothing lies before the prompt: masked, at positions < 0
                    attn = _window_attention(
                        q[0], k[0], v[0],
                        jnp.zeros((kind.window,) + k.shape[2:], k.dtype),
                        jnp.zeros((kind.window,) + v.shape[2:], v.dtype),
                        0, kind.window, sink,
                    )[None]
                    pk = ring_write(
                        pk, layer, page_ids[name], 0, stored(k[0])
                    )
                    pv = ring_write(
                        pv, layer, page_ids[name], 0, stored(v[0], v_dim)
                    )
                else:
                    # causal self-attention over the prompt
                    groups = cfg.n_heads // kh
                    qh = q.reshape(1, t_pad, kh, groups, cfg.head_dim)
                    scores = jnp.einsum(
                        "bthgd,bshd->bhgts",
                        qh.astype(jnp.float32),
                        k[0][None].astype(jnp.float32),
                    ) / jnp.sqrt(cfg.head_dim)
                    causal = (
                        jnp.arange(t_pad)[None, :] <= jnp.arange(t_pad)[:, None]
                    )
                    scores = jnp.where(
                        causal[None, None, None], scores, -1e30
                    )
                    probs = jax.nn.softmax(scores, axis=-1)
                    attn = jnp.einsum(
                        "bhgts,bshd->bthgd", probs,
                        v[0][None].astype(jnp.float32),
                    ).reshape(1, t_pad, -1)
                    pk = write_pages(
                        pk, layer, page_ids[name], stored(k[0])
                    )
                    pv = write_pages(
                        pv, layer, page_ids[name], stored(v[0], v_dim)
                    )
                return attn, {
                    **cache, "k": {**pool_k, name: pk},
                    "v": {**pool_v, name: pv},
                }

            # nothing lies before the prompt, whoever held the slot
            rows = jax.tree.map(jnp.zeros_like, slot_rows(state, slot))
            h, cache, moe = tfm.run_stack(
                cfg, params["blocks"], h, pos[None],
                {"k": pool_k, "v": pool_v, "rows": rows}, attend,
                shift=shift, recur=recur,
            )
            cache["state"] = put_slot_rows(state, slot, cache["rows"])
            return results((last_logits(params, h, true_len), moe), cache)

        @functools.partial(
            jax.jit, static_argnames=("t_pad",), donate_argnames=_POOL
        )
        def prefill_suffix(
            params,
            pool_k,
            pool_v,
            tokens,
            t_pad,
            hist_len,
            table,
            suffix_page_ids,
            state,
            slot,
            true_len,
        ):
            """Prefill the SUFFIX of a sequence whose first ``hist_len``
            tokens' KV is in its pages already (restored from the shared
            prefix cache, or written by the prompt's earlier chunks):
            write the suffix KV into its pages, then attend over history +
            suffix by walking the slot's page table in blocks of whole
            pages as far as the keys go (``_table_attention``: fixed
            shapes a trip, a traced count of trips; ``hist_len``, a
            multiple of ``page``, is traced, so one program serves every
            split within a suffix-length bucket). A windowed
            layer reads the window before the suffix out of the slot's
            ring, then writes the suffix's last pages over it. A
            convolution or delta layer takes row ``slot`` of its state,
            which the sequence's earlier run left there, as the state
            before the suffix, and leaves there the state of the suffix's
            first ``true_len`` tokens. tokens:
            int32[t_pad] padded suffix; table by class: ``full``
            int32[P_max], ``window`` the ring; suffix_page_ids:
            int32[t_pad // page] of the ``full`` class. Returns (logits
            of the suffix's last real token, int32[2] expert counts)."""
            pos = hist_len + jnp.arange(t_pad)  # absolute positions
            h = tfm.embed(cfg, params, tokens)[None]

            def shift(state, layer, s, cache):
                return shift_sequence(state, layer, s, cache, true_len)

            def recur(state, layer, q, k, v, g, beta, cache):
                return scan_sequence(
                    state, layer, q, k, v, g, beta, cache, true_len
                )

            def attend(kind, layer, q, k, v, sink, cache):
                pool_k, pool_v = cache["k"], cache["v"]
                name, kh = kind.name, kind.kv_heads
                pk, pv = pool_k[name], pool_v[name]
                if kind.window:
                    w = kind.window
                    before = hist_len - w + jnp.arange(w)  # may be < 0

                    def history(pool, width):
                        held = pool[layer][:, table[name]].reshape(
                            kh, ring, -1
                        )
                        return jnp.transpose(
                            held[:, before % ring, :width], (1, 0, 2)
                        )

                    attn = _window_attention(
                        q[0], k[0], v[0], history(pk, cfg.head_dim),
                        history(pv, cfg.v_head_dim), hist_len, w, sink,
                    )[None]
                    pk = ring_write(
                        pk, layer, table[name], hist_len // page,
                        stored(k[0]),
                    )
                    pv = ring_write(
                        pv, layer, table[name], hist_len // page,
                        stored(v[0], v_dim),
                    )
                else:
                    # scatter the suffix KV into its pages (prefill layout)
                    pk = write_pages(
                        pk, layer, suffix_page_ids, stored(k[0])
                    )
                    pv = write_pages(
                        pv, layer, suffix_page_ids, stored(v[0], v_dim)
                    )
                    # history + suffix keys through the slot's table, the
                    # suffix's own among them, as far as they go: what lies
                    # past hist_len + t_pad is not read, and key positions
                    # past hist_len + q_pos are masked
                    qh = q[0].reshape(t_pad, kh, cfg.n_heads // kh, -1)
                    attn = _table_attention(
                        stored(qh), pk, pv, layer, table[name], pos,
                        cfg.head_dim,
                    )[..., : cfg.v_head_dim].reshape(t_pad, -1)[None]
                return attn, {
                    **cache, "k": {**pool_k, name: pk},
                    "v": {**pool_v, name: pv},
                }

            rows = slot_rows(state, slot)
            h, cache, moe = tfm.run_stack(
                cfg, params["blocks"], h, pos[None],
                {"k": pool_k, "v": pool_v, "rows": rows}, attend,
                shift=shift, recur=recur,
            )
            cache["state"] = put_slot_rows(state, slot, cache["rows"])
            return results((last_logits(params, h, true_len), moe), cache)

        self._decode_step = decode_step
        self._prefill = prefill
        self._prefill_suffix = prefill_suffix

    # ------------------------------------------------------------------
    # scheduler
    # ------------------------------------------------------------------
    def submit(self, prompt: List[int], gen: GenerationConfig) -> int:
        t_wait = time.perf_counter()
        with self._lock:
            waited = time.perf_counter() - t_wait
            req = self._submit_locked(prompt, gen)
            req.span.set(submit_lock_wait_ms=waited * 1e3)
            return req.req_id

    def _submit_locked(self, prompt: List[int], gen: GenerationConfig):
        if self._swapping and self._swap_started is not None:
            from ray_tpu.config import cfg

            deadline = float(cfg.serve_swap_drain_deadline_s)
            if deadline > 0 and (
                time.monotonic() - self._swap_started > deadline
            ):
                # the drain has outlived its budget: stop parking — the
                # caller gets a typed, retryable rejection instead of an
                # unbounded hang behind one wedged slot
                from ray_tpu.serve.admission import Overloaded

                raise Overloaded(
                    reason="weights_swap",
                    retry_after_s=min(deadline, 5.0),
                )
        prompt_pages = -(-max(len(prompt), 1) // self.page)
        if prompt_pages > self.max_pages_per_seq:
            raise ValueError(
                f"prompt of {len(prompt)} tokens needs {prompt_pages} pages "
                f"but max_pages_per_seq={self.max_pages_per_seq} "
                f"(page_size={self.page})"
            )
        req = self._begin_request(list(prompt), gen)
        self.queue.append(req)
        return req

    def _begin_request(self, prompt: List[int], gen: GenerationConfig):
        req = _Request(self._next_req, prompt, gen)
        self._next_req += 1
        req.span = tracing.span(
            "engine.request", "engine", rid=req.req_id,
            prompt_tokens=len(prompt),
        ).begin()
        req.t_submit = time.perf_counter()
        self._live[req.req_id] = req
        return req

    def _end_request(self, rid: int, end: str, new_tokens: int) -> None:
        """The engine is done with ``rid`` (``end``: finished, cancelled
        or evicted): close its ``engine.request`` span."""
        req = self._live.pop(rid, None)
        if req is None:
            return
        admitted = req.t_admit or time.perf_counter()
        req.span.end(
            new_tokens=new_tokens,
            queue_wait_ms=(admitted - req.t_submit) * 1e3,
            prefill_ms=(
                (req.t_first - req.t_admit) * 1e3 if req.t_first else 0.0
            ),
            lock_wait_ms=req.lock_wait_s * 1e3,
            lock_wait_max_ms=req.lock_wait_max_s * 1e3,
            lock_acquires=req.lock_acquires,
            end=end,
        )

    def _pages_needed(self, req: _Request) -> Dict[str, int]:
        """Pages of each class to reserve at admission: the whole answer's
        of the class that grows with the context, a ring of the other."""
        need = self.pool.need(len(req.prompt) + req.gen.max_new_tokens)
        return {name: min(n, self._table_len(name)) for name, n in need.items()}

    def _tables(self, pages: Dict[str, List[int]]) -> Dict[str, np.ndarray]:
        """A slot's table of pages, by class; unfilled entries name the
        scratch page."""
        tables = {}
        for name, ids in pages.items():
            tables[name] = np.zeros(self._table_len(name), np.int32)
            tables[name][: len(ids)] = ids
        return tables

    def _admit(self) -> None:
        """Fill free slots from the queue while pages are available."""
        if self._swapping:
            # weights hot-swap drain: active slots finish on the OLD
            # weights-epoch, the queue stays parked until the new
            # weights are installed — no request ever mixes epochs
            return
        live = sum(s.active for s in self.slots)
        if not self.queue or live == self.B:
            return
        with tracing.span("engine.admit", "engine", live=live) as sp:
            admitted, short = self._admit_queued()
            self.admit_pool_stalls += short is not None
            # pool_stall: 0, or 1 with the class of page that was short
            sp.set(admitted=admitted, pool_stall=int(short is not None))
            if short is not None:
                sp.set(pool_stall_class=short)

    def _admit_queued(self):
        """``_admit``'s loop. Returns how many requests it admitted and
        the class of page that stalled it (a free slot and a queued
        request, and the pool had not the pages of that class), else
        ``None``."""
        admitted = 0
        for si, slot in enumerate(self.slots):
            if slot.active or not self.queue:
                continue
            req = self.queue[0]
            need = self._pages_needed(req)
            pages = self.pool.alloc(need)
            if pages is None:
                # backpressure: the POOL is the capacity
                return admitted, self.pool.short(need)
            self.queue.popleft()
            admitted += 1
            req.t_admit = time.perf_counter()
            req.first_step = self._steps
            req.span.set(slot=si)
            self.queue_wait_s += req.t_admit - req.t_submit
            prompt = req.prompt
            t = len(prompt)
            # shared prefix cache: restore the longest cached page-aligned
            # prefix as pinned shm views, capped so the LAST real token
            # always runs a live forward pass (its logits seed sampling)
            hit = None
            if self.prefix_cache is not None and t > 1:
                hit = self.prefix_cache.lookup(
                    prompt, max_tokens=((t - 1) // self.page) * self.page
                )
            tables = self._tables(pages)
            if hit is not None:
                last_logits = self._admit_with_prefix(
                    req, pages["full"], tables, hit
                )
            else:
                last_logits = self._prefill_prompt(prompt, pages, tables, si)
            if self.prefix_cache is not None:
                # publish this prompt's full pages for other replicas
                # (reads the pool AFTER prefill wrote it — the np gather
                # below is also what synchronizes the device work)
                self._prefix_insert(
                    prompt, pages["full"], hit.tokens if hit is not None else 0
                )
            first = self._sample_first(req.gen, last_logits, t)
            self._settle_prefill_counts()
            req.t_first = time.perf_counter()
            if hit is not None:
                # np conversions above synced every consumer of the
                # pinned views; dropping them releases the arena pin
                hit.release()
            slot.active = True
            slot.req_id = req.req_id
            slot.pos = t
            # the prefill already produced token #1, so decode runs
            # max_new-1 steps; the last token is never written back
            slot.max_pos = min(
                t + req.gen.max_new_tokens - 1, self._capacity(pages)
            )
            slot.pages = pages
            slot.eos = req.gen.eos_token
            slot.out = [first]
            # device state (the tables were built before prefill — the
            # suffix path passes the whole rows to its gathers)
            self._set_tables(si, tables)
            self.positions = self.positions.at[si].set(t)
            self.cur_tokens = self.cur_tokens.at[si].set(first)
            self.active_mask = self.active_mask.at[si].set(True)
            self.temps = self.temps.at[si].set(float(req.gen.temperature))
            self.seeds = self.seeds.at[si].set(
                np.uint32(req.gen.seed & 0xFFFFFFFF)
            )
            self._maybe_finish(si)
        return admitted, None

    def _capacity(self, pages: Dict[str, List[int]]) -> int:
        """Tokens the reserved pages hold: those of the class that grows
        (a ring holds any length)."""
        if "full" in pages:
            return len(pages["full"]) * self.page
        return self.cfg.max_seq_len

    def _set_tables(self, si: int, tables: Dict[str, np.ndarray]) -> None:
        self.block_tables = {
            name: self.block_tables[name].at[si].set(jnp.asarray(row))
            for name, row in tables.items()
        }

    def _write_pool(self, program):
        """Run ``program(pool_k, pool_v, state)``, a call of one of the
        programs that write the pool or the state by slot, and rebind
        them from its results after the first. This is the only place that
        holds their arrays: the programs take them donated, so the arrays
        passed in are dead once the call is made. Returns the program's
        first result."""
        k, v, state = self.pool.k, self.pool.v, self.pool.state
        try:
            out = program(k, v, state)
        except Exception as e:
            if any(a.is_deleted() for a in jax.tree.leaves((k, v, state))):
                raise KVPoolLost(
                    "the KV pool was donated to a program that then failed "
                    f"({type(e).__name__}: {e}); the pages of "
                    f"{sum(s.active for s in self.slots)} live slots are "
                    "lost and this engine must be rebuilt"
                ) from e
            raise
        self.pool.k, self.pool.v, *state = out[1:]
        if state:
            (self.pool.state,) = state
        return out[0]

    def _scatter(self, pages, k_src, v_src) -> None:
        """Whole pages computed elsewhere into the ``full`` class."""
        self._write_pool(
            lambda k, v, _: (None, *_scatter_pages(k, v, pages, k_src, v_src))
        )

    def _prefill_prompt(self, prompt, pages, tables, slot: int = 0):
        """Prefill the whole (padded) prompt, its KV written into the
        first of ``pages`` and the state of its true end into row ``slot``
        of the state by slot: one run of the prefill program up to
        ``max_prefill_tokens``; a longer prompt's head through it and the
        rest through the history-plus-suffix program in chunks of
        ``prefill_chunk`` tokens, so that no temporary grows with the
        square of the length. Returns the last real token's logits."""
        t = len(prompt)
        t_pad = max(self.page, -(-t // self.page) * self.page)
        tokens = np.zeros(t_pad, np.int32)
        tokens[:t] = prompt
        chunk = self.prefill_chunk
        chunks = max(0, -(-(t_pad - self.max_prefill_tokens) // chunk))
        head = t_pad - chunks * chunk
        # page ids go to the programs as host int32 arrays: a list through
        # ``jnp.asarray(..., dtype=int32)`` compiles a conversion of its
        # own for every new length
        page_ids = {
            name: np.asarray(
                ids if self.pool.classes[name].window
                else ids[: head // self.page],
                np.int32,
            )
            for name, ids in pages.items()
        }
        # keys a layer of the ``full`` class scores for the prompt's chunks,
        # whole blocks as far as each chunk's keys go, beside the keys the
        # slot's table holds, as many times
        block = self.page * _attention_block_pages(
            chunk, self.cfg.n_heads, self.page, self.max_pages_per_seq
        )
        with tracing.span(
            "engine.prefill", "engine", t_pad=t_pad, true_len=t,
            hit_tokens=0, chunks=1 + chunks, head=head,
            attn_keys_walked=sum(
                -(-(at + chunk) // block) * block
                for at in range(head, t_pad, chunk)
            ),
            attn_keys_table=chunks * self.max_pages_per_seq * self.page,
        ) as sp:
            if self.stateful:
                # rows of state written: one a layer that keeps state a run
                # of a program, the last run's at the prompt's true end
                sp.set(state_written=self.cfg.state_layers * (1 + chunks))
            kinds = self.cfg.state_kinds()
            scan_layers = kinds.get("delta", 0) + kinds.get("ssm", 0)
            if scan_layers:
                # blocks of the chunked scan (the delta rule's, a parallel
                # layer's Mamba-2 mixer's), a layer a run
                block = tfm.DELTA_BLOCK
                sp.set(scan_blocks=scan_layers * (
                    -(-head // block) + chunks * -(-chunk // block)
                ))
            slot = np.int32(slot)
            logits, moe = self._write_pool(
                lambda k, v, state: self._prefill(
                    self.params, k, v, jnp.asarray(tokens[:head]), head,
                    page_ids, state, slot, np.int32(min(t, head)),
                )
            )
            pairs = [moe]
            if chunks:
                dev_tables = {
                    n: jnp.asarray(row) for n, row in tables.items()
                }
            for at in range(head, t_pad, chunk):
                logits, moe = self._prefill_chunk(
                    tokens[at : at + chunk], at, dev_tables, pages, slot,
                    min(t - at, chunk),
                )
                pairs.append(moe)
            # read once the first token is (``_settle_prefill_counts``)
            self._prefill_counts = (sp, pairs)
        self.full_prefill_count += 1
        return logits

    def _settle_prefill_counts(self) -> None:
        """``moe_pairs_held`` of the newest ``engine.prefill`` span, read
        after the wait for the prefill's logits (the ring's record shares
        the span's args)."""
        sp, counts = self._prefill_counts or (None, ())
        self._prefill_counts = None
        if sp and self.cfg.n_routed_experts:
            sp.set(moe_pairs_held=sum(int(m[0]) for m in counts))

    def _prefill_chunk(self, tokens, hist_len: int, dev_tables, pages,
                       slot, true_len: int):
        """One run of the history-plus-suffix program over ``tokens``
        (padded to whole pages; the first ``true_len`` are real), the
        sequence's first ``hist_len`` tokens (whole pages) being in its
        pages, and their state in row ``slot``, already."""
        t_pad = len(tokens)
        first = hist_len // self.page
        suffix_pages = np.asarray(
            pages.get("full", [])[first : first + t_pad // self.page],
            np.int32,
        )
        return self._write_pool(
            lambda k, v, state: self._prefill_suffix(
                self.params, k, v, jnp.asarray(tokens), t_pad,
                jnp.int32(hist_len), dev_tables, suffix_pages, state, slot,
                np.int32(true_len),
            )
        )

    def _admit_with_prefix(self, req, pages, tables, hit):
        """Cache-hit admission: copy the pinned KV views into this
        engine's pool pages (``pages``: the slot's, of the ``full`` class)
        and prefill only the suffix. Returns the last real token's
        logits."""
        hist_pages = hit.tokens // self.page
        dev_pages = np.asarray(pages[:hist_pages], np.int32)
        # device-frame hits are ALREADY jax Arrays (landed straight from
        # the arena page — the device plane removed the intermediate
        # host copy); host-view hits keep the old path, where
        # jnp.asarray may alias the pinned view on the CPU backend —
        # safe because every consumer below is synced before release()
        k_src, v_src = hit.k, hit.v
        if isinstance(k_src, np.ndarray):
            k_src = jnp.asarray(np.asarray(k_src))
        if isinstance(v_src, np.ndarray):
            v_src = jnp.asarray(np.asarray(v_src))
        self._scatter(dev_pages, k_src, v_src)
        suffix = req.prompt[hit.tokens :]
        ts = len(suffix)
        t_pad = max(self.page, -(-ts // self.page) * self.page)
        tokens = np.zeros(t_pad, np.int32)
        tokens[:ts] = suffix
        with tracing.span(
            "engine.prefill", "engine", t_pad=t_pad, true_len=ts,
            hit_tokens=int(hit.tokens), chunks=1,
        ):
            logits, _ = self._prefill_chunk(
                tokens, int(hit.tokens),
                {n: jnp.asarray(row) for n, row in tables.items()},
                {"full": pages}, np.int32(0), ts,
            )
        return logits

    def _prefix_insert(self, prompt, pages, covered: int) -> None:
        """Publish the prompt's FULL pages (already in the pool) to the
        shared cache — skipped when the hit already covered them."""
        with tracing.span("engine.prefix_insert", "engine", pages=0) as sp:
            ins = (len(prompt) // self.page) * self.page
            if ins <= covered or ins == 0:
                return
            n_pages = ins // self.page
            if n_pages > len(pages):
                return
            if getattr(self.prefix_cache, "contains_prefix", None) and (
                self.prefix_cache.contains_prefix(prompt[:ins])
            ):
                # already published (hot prompt): skip the device→host KV
                # gather entirely — it's a blocking sync on the admit path
                return
            dev = np.asarray(pages[:n_pages], np.int32)
            from ray_tpu.cluster import device_plane as _dp

            if _dp.device_plane_enabled():
                # the gathered KV block stays a device buffer: the cache's
                # seal exports it as a device frame (zero-copy where the
                # backend aliases host memory, chunked D2H pump elsewhere)
                # — the eager np.asarray device→host sync is gone from the
                # admit path, and lookups on the other side land the pages
                # back on device with one device_put
                k, v = _gather_pages(self.pool.k, self.pool.v, dev)
            else:
                k, v = map(
                    np.asarray, _gather_pages(self.pool.k, self.pool.v, dev)
                )
            self.prefix_cache.insert(prompt[:ins], k, v)
            sp.set(pages=n_pages)

    def _sample_first(self, gen: GenerationConfig, last_logits, t: int) -> int:
        # the int() below is where the host waits for the prefill's logits
        with tracing.span("engine.first_token", "engine"):
            if gen.temperature > 0.0:
                # same uint32 normalization as the decode path — one key
                # stream per request across prefill and decode
                kk = jax.random.fold_in(
                    jax.random.PRNGKey(np.uint32(gen.seed & 0xFFFFFFFF)),
                    t,
                )
                return int(
                    jax.random.categorical(
                        kk,
                        jnp.asarray(last_logits)
                        / max(gen.temperature, 1e-6),
                    )
                )
            return int(np.asarray(last_logits).argmax())

    # ------------------------------------------------------------------
    # disaggregated serving: prefill/decode split (PR 18)
    # ------------------------------------------------------------------
    @_locked
    def prefill_extract(self, prompt: List[int], gen: GenerationConfig):
        """Prefill-worker half of the KV handoff: run the bucketed
        prefill program for ``prompt``, sample the first token
        (host-side, per-request deterministic — the same
        ``fold_in(seed, t)`` stream a monolithic admit uses), gather the
        prompt pages out of the pool, and free them. Returns
        ``(manifest, k, v)`` where ``k``/``v`` are
        ``[L, KH, prompt_pages, page, hd]`` blocks — device buffers when
        the device plane is on (the wire layer seals them as device
        frames, so the ship to a decode replica rides the striped
        peer-socket plane and lands with one ``device_put``), host
        copies otherwise (the host-bounce fallback)."""
        self._refuse_windowed("prefill_extract (the KV hand-off)")
        t = len(prompt)
        if t < 1:
            raise ValueError("prefill_extract needs a non-empty prompt")
        t_pad = max(self.page, -(-t // self.page) * self.page)
        prompt_pages = t_pad // self.page
        if prompt_pages > self.max_pages_per_seq:
            raise ValueError(
                f"prompt of {t} tokens needs {prompt_pages} pages but "
                f"max_pages_per_seq={self.max_pages_per_seq}"
            )
        pages = self.pool.alloc({"full": prompt_pages})
        if pages is None:
            raise MemoryError(
                "prefill pool exhausted "
                f"(free={self.pool.free_pages}, need={prompt_pages})"
            )
        try:
            first = self._sample_first(
                gen,
                self._prefill_prompt(prompt, pages, self._tables(pages)),
                t,
            )
            dev = np.asarray(pages["full"], np.int32)
            from ray_tpu.cluster import device_plane as _dp

            if _dp.device_plane_enabled():
                # functional jax arrays: these gathers are new buffers,
                # so freeing the pool pages below cannot alias them
                k, v = _gather_pages(self.pool.k, self.pool.v, dev)
            else:
                k, v = map(
                    np.asarray, _gather_pages(self.pool.k, self.pool.v, dev)
                )
        finally:
            self.pool.free(pages)
        manifest = {
            "prompt": list(prompt),
            "t": t,
            "first": int(first),
            "pages": prompt_pages,
            "page": self.page,
            "gen": {
                "max_new_tokens": int(gen.max_new_tokens),
                "temperature": float(gen.temperature),
                "seed": int(gen.seed),
                "eos_token": gen.eos_token,
            },
            "model": self.model_id,
            "weights_epoch": self.weights_epoch,
        }
        return manifest, k, v

    @_locked
    def adopt_pages(self, manifest: dict, k, v) -> Optional[int]:
        """Decode-engine half of the KV handoff: graft prefilled KV
        pages straight into this engine's pool and admit the request
        mid-batch — no prefill program runs here (the zero-re-prefill
        property the disagg bench gates on). Returns the new req_id, or
        None when the handoff cannot be adopted (mismatched page
        geometry or model, no free slot, pool backpressure) — the
        caller falls back to ``submit()``, i.e. a local re-prefill,
        which is token-exact because generation is seed-deterministic."""
        self._refuse_windowed("adopt_pages (the KV hand-off)")
        if manifest.get("page") != self.page:
            return None
        if manifest.get("model", self.model_id) != self.model_id:
            # KV computed under different weights: grafting it would mix
            # weights-epochs inside one batch — refuse, re-prefill
            return None
        gen = GenerationConfig(**manifest["gen"])
        prompt = list(manifest["prompt"])
        t = int(manifest["t"])
        ship_pages = int(manifest["pages"])
        si = next(
            (i for i, s in enumerate(self.slots) if not s.active), None
        )
        if si is None:
            return None
        need = min(
            -(-(t + gen.max_new_tokens) // self.page),
            self.max_pages_per_seq,
        )
        need = max(need, ship_pages)
        if need > self.max_pages_per_seq:
            return None
        pages = self.pool.alloc({"full": need})
        if pages is None:
            return None  # pool backpressure: the POOL is the capacity
        req = self._begin_request(prompt, gen)
        # grafted mid-batch: no queue, no prefill here
        req.t_admit = req.t_first = req.t_submit
        req.first_step = self._steps
        req.span.set(slot=si)
        rid = req.req_id
        dev = np.asarray(pages["full"][:ship_pages], np.int32)
        self._scatter(dev, k, v)
        first = int(manifest["first"])
        slot = self.slots[si]
        slot.active = True
        slot.req_id = rid
        slot.pos = t
        slot.max_pos = min(
            t + gen.max_new_tokens - 1, self._capacity(pages)
        )
        slot.pages = pages
        slot.eos = gen.eos_token
        slot.out = [first]
        self._set_tables(si, self._tables(pages))
        self.positions = self.positions.at[si].set(t)
        self.cur_tokens = self.cur_tokens.at[si].set(first)
        self.active_mask = self.active_mask.at[si].set(True)
        self.temps = self.temps.at[si].set(float(gen.temperature))
        self.seeds = self.seeds.at[si].set(
            np.uint32(gen.seed & 0xFFFFFFFF)
        )
        self.adopted_count += 1
        self._maybe_finish(si)
        return rid

    # ------------------------------------------------------------------
    # weights hot-swap (PR 18 model multiplexing)
    # ------------------------------------------------------------------
    def swap_params(self, params: Any, model_id: Optional[str] = None) -> int:
        """Install new weights with epoch-fenced drain semantics (the
        gang-epoch pattern applied to a replica's weights): admission
        parks, every ACTIVE slot finishes its generation on the old
        weights-epoch, then the swap lands and the epoch bumps — no
        in-flight stream ever crosses weights. Queued requests stay
        queued and admit on the NEW weights. Returns the new epoch.

        The drain is bounded by ``cfg.serve_swap_drain_deadline_s``
        (0 = legacy unbounded): past the deadline, still-active slots are
        force-evicted — their output is recorded truncated at the tokens
        generated so far, so a wedged generation can park the whole
        replica for at most one deadline, never forever."""
        from ray_tpu.config import cfg

        self._refuse_windowed("swap_params (the weights hot-swap)")
        deadline = float(cfg.serve_swap_drain_deadline_s)
        self._swapping = True
        self._swap_started = time.monotonic()
        try:
            while any(s.active for s in self.slots):
                if deadline > 0 and (
                    time.monotonic() - self._swap_started > deadline
                ):
                    self._force_evict_active()
                    break
                self.step()
            self.params = params
            if model_id is not None:
                self.model_id = model_id
            self.weights_epoch += 1
            if self.prefix_cache is not None:
                # KV cached under the OLD weights must never be restored
                # for the new ones — re-namespace the shared cache so
                # every stale prefix misses (engines swapping to the
                # same model id keep sharing the new namespace)
                self.prefix_cache.retag(
                    self.model_id
                    if model_id is not None
                    else f"swap{self.weights_epoch}"
                )
        finally:
            self._swapping = False
            self._swap_started = None
        return self.weights_epoch

    @_locked
    def _force_evict_active(self) -> None:
        """Evict every still-active slot at the swap-drain deadline: the
        partial output lands in results (eos-truncated like a normal
        finish) so readers unblock, pages free, and the slot resets."""
        for si, slot in enumerate(self.slots):
            if not slot.active:
                continue
            out = slot.out
            if slot.eos is not None and slot.eos in out:
                out = out[: out.index(slot.eos)]
            self.results[slot.req_id] = out
            self._end_request(slot.req_id, "evicted", len(slot.out))
            self.pool.free(slot.pages)
            self.slots[si] = _Slot()
            self.active_mask = self.active_mask.at[si].set(False)
            self.swap_force_evicted += 1

    def _maybe_finish(self, si: int) -> None:
        slot = self.slots[si]
        done = (
            slot.pos >= slot.max_pos
            or (slot.eos is not None and slot.out and slot.out[-1] == slot.eos)
        )
        if done and slot.active:
            out = slot.out
            if slot.eos is not None and slot.eos in out:
                out = out[: out.index(slot.eos)]
            self.results[slot.req_id] = out
            self._end_request(slot.req_id, "finished", len(slot.out))
            self.pool.free(slot.pages)
            self.slots[si] = _Slot()
            self.active_mask = self.active_mask.at[si].set(False)

    @_locked
    def step(self) -> List[int]:
        """Admit + one decode step for all active slots. Returns req_ids
        finished in this step."""
        with tracing.span("engine.step", "engine") as stepping:
            self._admit()
            before = set(self.results)
            live = [s for s in self.slots if s.active]
            if live:
                decode = tracing.span("engine.decode", "engine")
                if decode:
                    page = self.page
                    written = [-(-(s.pos + 1) // page) for s in live]
                    # pages_reserved / pages_written: of the class that
                    # grows with the context; full_pages / window_pages:
                    # pages that hold a live token, by class
                    decode.set(
                        live=len(live),
                        slots=self.B,
                        ctx=sum(s.pos + 1 for s in live),
                        pages_reserved=sum(
                            len(s.pages.get("full", ())) for s in live
                        ),
                        pages_written=sum(written),
                        queued=len(self.queue),
                    )
                    if self.windowed or self.stateful:
                        decode.set(full_pages=sum(written))
                    if self.stateful:
                        # each live slot's state read once and written once,
                        # all of it and a parallel layer's Mamba-2 state's
                        decode.set(state_bytes=(
                            2 * len(live) * self.pool.state_bytes_per_slot
                        ))
                        ssm = self.pool.slot_bytes.get("ssm_s")
                        if ssm is not None:
                            decode.set(ssm_state_bytes=2 * len(live) * (
                                ssm + self.pool.slot_bytes["ssm_taps"]
                            ))
                    if self.windowed:
                        decode.set(
                            window_pages=sum(
                                min(n, self.pool.ring_pages) for n in written
                            ),
                        )
                with decode:
                    nxt, counts = self._write_pool(
                        lambda k, v, state: self._decode_step(
                            self.params, k, v, self.block_tables,
                            self.positions, self.cur_tokens,
                            self.active_mask, self.temps, self.seeds, state,
                        )
                    )
                if decode:
                    counts.copy_to_host_async()  # beside the tokens' copy
                # where the host waits for the step's tokens
                with tracing.span("engine.readback", "engine"):
                    nxt_h = np.asarray(nxt)
                if stepping:
                    self._step_done[self._steps % STEP_STAMPS] = (
                        time.perf_counter()
                    )
                if decode:
                    # the step's sums are known once its tokens are: the
                    # ring's record shares the span's args
                    pairs, hit, in_kernel, full, walked, entries, *rest = (
                        np.asarray(counts).tolist()
                    )
                    stated = rest[:2] if self.stateful else ()
                    decode.set(
                        attn_kernel_layers=in_kernel, attn_full_layers=full,
                        attn_pages_walked=walked, attn_table_entries=entries,
                    )
                    if stated:
                        decode.set(
                            state_layers=stated[0],
                            state_slots_written=stated[1],
                        )
                    if self.recurrent:
                        decode.set(state_kernel_layers=rest[2])
                    if self.cfg.n_routed_experts:
                        decode.set(
                            moe_pairs_held=pairs, moe_experts_hit=hit,
                            moe_kernel_layers=rest[-1],
                        )
                self.positions = self.positions + jnp.where(
                    self.active_mask, 1, 0
                )
                self.cur_tokens = nxt
                for si, slot in enumerate(self.slots):
                    if not slot.active:
                        continue
                    slot.pos += 1
                    slot.out.append(int(nxt_h[si]))
                    self._maybe_finish(si)
            self._steps += 1
            return [r for r in self.results if r not in before]

    def pending(self) -> int:
        return len(self.queue) + sum(s.active for s in self.slots)

    # ------------------------------------------------------------------
    def generate_ids(
        self,
        prompts: List[List[int]],
        gen: GenerationConfig = GenerationConfig(),
    ) -> List[List[int]]:
        ids = [self.submit(p, gen) for p in prompts]
        while any(i not in self.results for i in ids):
            self.step()
        return [self.results.pop(i) for i in ids]

    def stream_ids(
        self,
        prompt: List[int],
        gen: GenerationConfig = GenerationConfig(),
    ):
        """Incremental generation: yields token ids as decode steps produce
        them (the engine keeps serving any other in-flight requests in the
        same steps). The serving tier pipes this through a
        ray_tpu.experimental Channel for cross-process token streaming."""
        rid = self.submit(prompt, gen)
        yield from self.stream_rid(rid)

    def stream_rid(self, rid: int):
        """Stream tokens for an already-registered request id — either
        one queued via ``submit()`` or one grafted mid-batch via
        ``adopt_pages()`` (the disaggregated handoff path, where no
        local prefill ever runs)."""
        yielded = 0
        req = self._live.get(rid)
        # how long its tokens wait for this consumer once they are on the
        # host: measured while the request has a span
        traced = req is not None and bool(req.span)
        try:
            while True:
                t_wait = time.perf_counter()
                with self._lock:
                    self._note_lock_wait(req, time.perf_counter() - t_wait)
                    if rid in self.results:
                        break
                    self.step()
                    slot = next(
                        (
                            s for s in self.slots
                            if s.req_id == rid and s.active
                        ),
                        None,
                    )
                    out = list(slot.out) if slot is not None else []
                    if slot is not None and slot.eos in out:
                        out = out[: out.index(slot.eos)]
                while yielded < len(out):
                    if traced:
                        self._note_pickup(req, yielded)
                    yield out[yielded]
                    yielded += 1
            final = self.results.pop(rid)
            while yielded < len(final):
                if traced:
                    self._note_pickup(req, yielded)
                yield final[yielded]
                yielded += 1
        finally:
            if traced:
                # the ring's record shares the span's args: this lands
                # there though the engine ended the span before
                req.span.set(
                    pickup_lag_ms=req.pickup_lag_s * 1e3,
                    pickup_lag_max_ms=req.pickup_lag_max_s * 1e3,
                )
            # consumer abandoned mid-stream: reclaim the slot's pages and
            # stop burning decode steps on a dead client
            self._cancel(rid)

    def _note_pickup(self, req: _Request, k: int) -> None:
        """Token ``k`` of ``req`` is handed to its consumer now: how long
        it has been on the host (``_Request.first_step``). Takes no lock: a
        stamp the ring has since overwritten (``STEP_STAMPS`` steps on) is
        skipped."""
        made = req.t_first
        if k:
            step = req.first_step + k - 1
            if self._steps - step >= STEP_STAMPS:
                return
            made = self._step_done[step % STEP_STAMPS]
        waited = max(0.0, time.perf_counter() - made)
        req.pickup_lag_s += waited
        req.pickup_lag_max_s = max(req.pickup_lag_max_s, waited)

    def _note_lock_wait(self, req: Optional[_Request], waited: float) -> None:
        """One acquisition of the engine lock by a request's stream (the
        caller holds the lock): how long its thread waited for it."""
        self.lock_wait_s += waited
        self.lock_acquires += 1
        if req is not None:
            req.lock_wait_s += waited
            req.lock_acquires += 1
            req.lock_wait_max_s = max(req.lock_wait_max_s, waited)

    @_locked
    def _cancel(self, rid: int) -> None:
        """Drop a request wherever it is: queued, active, or finished."""
        self.results.pop(rid, None)
        for i, req in enumerate(self.queue):
            if req.req_id == rid:
                del self.queue[i]
                self._end_request(rid, "cancelled", 0)
                return
        for si, slot in enumerate(self.slots):
            if slot.active and slot.req_id == rid:
                self._end_request(rid, "cancelled", len(slot.out))
                self.pool.free(slot.pages)
                self.slots[si] = _Slot()
                self.active_mask = self.active_mask.at[si].set(False)
                return

    def generate(
        self, prompts: List[str], gen: GenerationConfig = GenerationConfig()
    ) -> List[str]:
        enc = [self.tokenizer.encode(p) for p in prompts]
        if gen.eos_token is None:
            gen = GenerationConfig(
                max_new_tokens=gen.max_new_tokens,
                temperature=gen.temperature,
                seed=gen.seed,
                eos_token=getattr(self.tokenizer, "eos", None),
            )
        out = self.generate_ids(enc, gen)
        return [self.tokenizer.decode(ids) for ids in out]

    @_locked
    def stats(self) -> dict:
        out = {
            "free_pages": self.pool.free_pages,
            "total_pages": self.pool.n_pages,
            "active_slots": sum(s.active for s in self.slots),
            "queued": len(self.queue),
            "model_id": self.model_id,
            "weights_epoch": self.weights_epoch,
            "full_prefill_count": self.full_prefill_count,
            "adopted_count": self.adopted_count,
            "swap_force_evicted": self.swap_force_evicted,
            "admit_pool_stalls": self.admit_pool_stalls,
            "lock_wait_s": self.lock_wait_s,
            "lock_acquires": self.lock_acquires,
            "queue_wait_s": self.queue_wait_s,
        }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out
