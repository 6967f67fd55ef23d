"""The main path's kernels, compiled for a described TPU v5e at real widths.

No chip is attached and nothing runs: the TPU compiler installed here
compiles for a topology that is only described, and refuses what the
chip's compiler would refuse (a block that does not tile, more VMEM than a
kernel may stage, a Mosaic kernel the partitioner cannot split). Interpret
mode, which every other kernel test uses, sees none of that. A compile
that passes here is not a chip run; ``chip_smoke.py`` is.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in specs
    ]


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static
    ).compile()


# -- flash attention: [B, T, H, KH, D] of chip_smoke's train phase -----------


@pytest.mark.parametrize("t", [1024, 1023], ids=["T1024", "T1023-ragged"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention(one_chip, t, direction):
    from ray_tpu.ops.flash_attention import flash_attention

    q, k, v = _shapes(one_chip, *[((8, t, 16, 128), jnp.bfloat16)] * 3)
    if direction == "forward":
        fn = flash_attention
        want = 1
    else:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: flash_attention(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

        want = 3  # forward for the residuals, dq, dk/dv
    text = _compile(fn, q, k, v).as_text()
    assert text.count(f'"{KERNEL}"') == want


# -- the serving engine's programs at the benchmark's geometries ---------------

PAGE = 16


def _model(name):
    """The configurations' widths, slots and pages of the full class
    (``benchmarks/configs``), contexts to ``max_seq_len``."""
    from ray_tpu.models import transformer as tfm

    if name == "mistral":  # 8 KV heads, groups of 4, table 160
        return tfm.ModelConfig(
            vocab_size=32768, d_model=4096, n_layers=16, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq_len=2560, rope_theta=1e6,
            dtype=jnp.bfloat16,
        ), 32, 2048
    if name == "internlm2":  # 8 KV heads, groups of 2, table 96
        return tfm.ModelConfig(
            vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
            n_kv_heads=8, d_ff=8192, max_seq_len=1536, rope_theta=1e6,
            dtype=jnp.bfloat16,
        ), 32, 2560
    if name == "lfm2":  # 3 full layers of 8 KV heads x 64 stored 128 wide,
        # groups of 4, table 256; 11 convolution layers' state by slot
        return tfm.ModelConfig(
            vocab_size=65536, d_model=2048, n_layers=14, n_heads=32,
            n_kv_heads=8, d_ff=7168, max_seq_len=4096, rope_theta=1e6,
            dtype=jnp.bfloat16, rms_eps=1e-5,
            attn_pattern=("conv", "conv") + ("full", "conv", "conv", "conv") * 3,
            ffn_pattern=("dense",) * 2 + ("experts",) * 12,
            qk_norm=True, conv_kernel=3, tie_embeddings=True,
            d_ff_expert=1792, n_routed_experts=32, experts_per_token=4,
            router_norm_eps=1e-6,
        ), 64, 8192
    if name == "olmo":  # 4 full layers of 30 KV heads x 128 in groups of
        # one, table 1,056; 12 delta layers' float32 matrix a head by slot
        return tfm.ModelConfig(
            vocab_size=100352, d_model=3840, n_layers=16, n_heads=30,
            n_kv_heads=30, d_ff=11008, max_seq_len=16896, rope_theta=0.0,
            dtype=jnp.bfloat16,
            attn_pattern=("delta", "delta", "delta", "full") * 4,
            ffn_pattern=("dense",) * 16, qk_norm=True, qk_norm_whole=True,
            post_norm=True, conv_kernel=4, delta_heads=30, delta_key_dim=96,
            delta_value_dim=192, delta_neg_eigval=True,
        ), 16, 4096
    if name == "falcon":  # 6 parallel layers: attention of 20 heads on 4 KV
        # heads x 128 (groups of 5), table 256, and beside it the Mamba-2
        # mixer's float32 matrix a head by slot; a vocabulary of 261,120
        return tfm.ModelConfig(
            vocab_size=261120, d_model=5120, n_layers=6, n_heads=20,
            n_kv_heads=4, head_dim=128, d_ff=21504, max_seq_len=4096,
            rope_theta=1e11, dtype=jnp.bfloat16, rms_eps=1e-5,
            attn_pattern=("parallel",) * 6, ffn_pattern=("dense",) * 6,
            conv_kernel=4, ssm_heads=32, ssm_head_dim=128, ssm_state=256,
            ssm_groups=2, embedding_multiplier=5.656854249492381,
            lm_head_multiplier=0.0078125, attention_out_multiplier=0.0375,
            key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
            ssm_out_multiplier=0.08838834764831845,
            ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369,
                             0.5, 0.3535533905932738),
            mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        ), 64, 12288
    # mimo-v2.5-l7-ep16: a full class of 4 KV heads, groups of 16, keys of
    # 192 stored 256 wide, values 128, table 512; 64 rings of 9 pages
    return tfm.ModelConfig(
        vocab_size=19072, d_model=4096, n_layers=7, n_heads=64, n_kv_heads=4,
        d_ff=16384, max_seq_len=8192, rope_theta=1e7, dtype=jnp.bfloat16,
        rms_eps=1e-5, head_dim=192, v_head_dim=128, rotary_dim=64,
        value_scale=0.707,
        attn_pattern=("full",) + ("window",) * 5 + ("full",),
        ffn_pattern=("dense",) + ("experts",) * 6,
        window=128, window_kv_heads=8, window_rope_theta=1e4,
        window_sink=True, d_ff_expert=2048, n_routed_experts=256,
        experts_per_token=8, experts_held=(0, 16),
    ), 64, 16384


class _Deployment:
    """An engine built as on the chip, and its operands as shapes there:
    the pools at the cell's size, a table a class, the weights."""

    def __init__(self, name, sharding):
        from ray_tpu.llm.continuous import ContinuousBatchingEngine
        from ray_tpu.models import transformer as tfm

        self.cfg, self.slots, n_pages = _model(name)
        cfg, slots = self.cfg, self.slots
        table = cfg.max_seq_len // PAGE
        # the programs close over the geometry of a slot, not over the
        # pool's size: a pool of one slot's pages keeps the engine small.
        # The engine reads the platform to choose its decode attention, and
        # the attached backend here is the CPU
        def shape(dims, dtype):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

        self.shape = shape
        # shapes in place of weights: the engine checks their layout only
        self.params = jax.tree.map(
            lambda x: shape(x.shape, x.dtype),
            jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))),
        )
        with pytest.MonkeyPatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            self.eng = ContinuousBatchingEngine(
                cfg, params=self.params, max_batch=slots, page_size=PAGE,
                n_pages=table + 1, max_pages_per_seq=table,
            )
        assert self.eng._attn_kernel == "compiled"
        self.pool_k, self.pool_v, self.tables = {}, {}, {}
        for cls, (layers, kind) in cfg.kv_classes().items():
            n = slots * self.eng.pool.ring_pages + 1 if kind.window else n_pages
            lead = (layers, kind.kv_heads, n, PAGE)
            self.pool_k[cls] = shape(lead + (self.eng.pool.k_dim,), cfg.dtype)
            self.pool_v[cls] = shape(lead + (self.eng.pool.v_dim,), cfg.dtype)
            self.tables[cls] = shape(
                (slots, self.eng._table_len(cls)), jnp.int32
            )
        self.pools = [*self.pool_k.values(), *self.pool_v.values()]
        # what the layers that are no attention keep, by slot
        self.state = jax.tree.map(
            lambda x: shape(x.shape, x.dtype), self.eng.pool.state
        )
        self._decode = None

    def ints(self, *dims):
        return self.shape(dims, jnp.int32)

    def _decode_operands(self):
        n = (self.slots,)
        return (
            self.params, self.pool_k, self.pool_v, self.tables,
            self.ints(*n), self.ints(*n), self.shape(n, jnp.bool_),
            self.shape(n, jnp.float32), self.shape(n, jnp.uint32),
            self.state,
        )

    def decode_step(self):
        """``decode_step`` compiled, once a deployment."""
        if self._decode is None:
            self._decode = self.eng._decode_step.lower(
                *self._decode_operands()
            ).compile()
        return self._decode

    def copies_of_a_pool(self, text):
        return [
            dims for dims in {",".join(map(str, p.shape)) for p in self.pools}
            if re.findall(rf"= bf16\[{dims}\]\S* copy\(", text)
        ]

    def copies_of_experts(self, text):
        """Operations of the optimised module that yield the experts of a
        layer ``[held, D, F]`` or of a run ``[L, held, D, F]``, the stacks
        themselves and views of them aside (a parameter, a bitcast, a
        tuple's element): as a scan's ``xs`` a layer's were written out
        every step (``dynamic-slice_bitcast_fusion``, a third of both sparse
        cells' device time; PERF.md section 6)."""
        cfg = self.cfg
        held, d, f = cfg.experts_held[1], cfg.d_model, cfg.d_ff_expert
        return re.findall(
            rf"(\S+) = bf16\[(?:\d+,)?{held},(?:{d},{f}|{f},{d})\]\S* "
            r"(?!bitcast\(|get-tuple-element\(|parameter\()([\w-]+)\(",
            text,
        )

    @staticmethod
    def state_kernels(text):
        """The state kernel's calls in the optimised module, each as (the
        shape its second result has, the shape of the operand that result
        aliases)."""
        found = []
        for line in text.splitlines():
            if (f'custom_call_target="{KERNEL}"' not in line
                    or "state_step_decode" not in line):
                continue
            result = re.search(r"= \((\S+), (f32\[[\d,]+\])", line).group(2)
            into = int(re.search(
                r"output_to_operand_aliasing=\{\{1\}: \((\d+),", line
            ).group(1))
            operands = re.search(
                r"operand_layout_constraints=\{(.*?)\}\}", line
            ).group(1)
            found.append((result, re.findall(r"\w+\[[\d,]*\]", operands)[into]))
        return found

    @staticmethod
    def readers_of_whole(text, dims):
        """Fusions handed the whole state ``f32[dims]`` as an operand: a
        fused computation's parameter of that shape. The parent's decode
        step had two a parallel layer (the readout, the select and update
        in place) and three a delta layer (``k S'``, ``q S``, the select)."""
        return re.findall(
            rf"%param\S* = f32\[{dims}\]\S* parameter\(", text
        )

    @staticmethod
    def grouped_matmuls(text):
        """(Megablox ``gmm`` kernels, ``lax.ragged_dot``'s operations) in
        the optimised module."""
        return (
            len(re.findall(r"^\s*%gmm[.\d]* = ", text, re.M)),
            len(re.findall(r"%ragged-dot", text)),
        )

    def scans_of_decode_step(self):
        """(shapes the layers' scans slice a layer of, shapes they close
        over whole) in ``decode_step`` as traced."""
        traced = self.eng._decode_step.trace(*self._decode_operands())
        sliced, whole = set(), set()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "scan":
                    consts = eqn.params["num_consts"]
                    xs = consts + eqn.params["num_carry"]
                    whole.update(v.aval.shape for v in eqn.invars[:consts])
                    sliced.update(v.aval.shape for v in eqn.invars[xs:])
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(traced.jaxpr.jaxpr)
        return sliced, whole


@pytest.fixture(scope="module")
def deployment(one_chip):
    made = {}

    def get(name):
        if name not in made:
            made[name] = _Deployment(name, one_chip)
        return made[name]

    return get


@pytest.mark.parametrize("name", ["mistral", "internlm2", "mixed"])
def test_decode_step_reads_live_pages_where_they_lie(deployment, name):
    """``decode_step`` at the three configurations' real geometries holds
    the paged-attention kernel, handed the pool whole, and nothing of the
    size of a gathered table ``[KH, B, P, page, .]`` (the copies and their
    feeding fusions were 4.5-5.9 s of 10 on the chip, PERF.md PR 31) nor of
    one layer's slice of the pool ``[KH, N, page, .]`` (1.4 s in
    reasoning): K and V are read page by page inside the kernel."""
    d = deployment(name)
    text = d.decode_step().as_text()
    kh, pool = d.cfg.n_kv_heads, d.pool_k["full"]
    kernels = [
        line for line in text.splitlines()
        if f'custom_call_target="{KERNEL}"' in line
        and "paged_attention_decode" in line
    ]
    whole = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    assert kernels and all(whole in line for line in kernels)
    slots, table = d.tables["full"].shape
    rows = slots * table
    for what, dims in {
        "a gathered table": rf"{kh},{slots},{table},{PAGE},\d+",
        "a gathered table, flat": rf"{rows},{kh},{PAGE},\d+",
        "a layer's slice of the pool": rf"{kh},{pool.shape[2]},{PAGE},\d+",
    }.items():
        assert not re.findall(rf"bf16\[{dims}\]", text), what
    assert not d.copies_of_a_pool(text)


@pytest.mark.parametrize(
    "kv_heads, table", [(16, 64), (32, 128)], ids=["smoke-16x128", "32x128"]
)
def test_paged_decode_sizes_its_buffers_to_the_pages_width(
    one_chip, kv_heads, table
):
    """chip_smoke's widths (8 slots, 16 KV heads x 128 with groups of 1,
    64 table entries) and twice as wide: 64 pages a buffer would be 16 and
    32 MiB of VMEM, which the chip's compiler refuses ("Ran out of memory in
    memory space vmem", as chip_smoke met it); the kernel takes fewer pages
    a chunk from its operands' shapes."""
    from ray_tpu.ops.paged_attention import paged_attention_decode

    pool = ((12, kv_heads, 2048, 16, 128), jnp.bfloat16)
    args = _shapes(
        one_chip, ((8, kv_heads, 1, 128), jnp.bfloat16), pool, pool,
        ((), jnp.int32), ((8, table), jnp.int32), ((8,), jnp.int32),
    )
    text = paged_attention_decode.lower(*args, scale=128**-0.5).compile().as_text()
    assert KERNEL in text


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_pool_writers_copy_no_pool(deployment, program):
    """``mistral-7b-v0.3-l16``'s cells: 32 slots, a [16,8,2048,16,128] bf16
    pool (1 GiB of K, 1 GiB of V), contexts to 2,560. A program that writes
    a few rows of the pool and returns it must alias it, not copy it: no
    ``copy`` of the pool's shape in the optimised module, and temporaries
    plus results that alias no operand smaller than one pool. Undonated,
    the same programs held ``copy.101``/``copy.102`` and 2 GiB of results
    of their own, 7 and 12 ms a step on the chip (PERF.md, PR 27)."""
    d = deployment("mistral")
    if program == "decode_step":
        compiled = d.decode_step()
    else:
        t_pad = 2048  # the longest prompt of the cells
        compiled = d.eng._prefill.lower(
            d.params, d.pool_k, d.pool_v, d.ints(t_pad), t_pad,
            {"full": d.ints(t_pad // PAGE)}, d.state, d.ints(), d.ints(),
        ).compile()
    assert not d.copies_of_a_pool(compiled.as_text())
    mem = compiled.memory_analysis()
    one_pool = 2 * d.pool_k["full"].size  # bytes of K alone
    assert mem.alias_size_in_bytes == 2 * one_pool
    own = mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert mem.temp_size_in_bytes + own < one_pool


# -- a stack by position at the widths of `mimo-v2.5-l7-ep16` ------------------


@pytest.mark.parametrize("program", ["decode_step", "prefill", "prefill_suffix"])
def test_two_page_classes_and_held_experts_copy_no_pool(deployment, program):
    """``mimo-v2.5-l7-ep16.mixed``: 64 slots, contexts to 8,192, a full
    class of 16,384 pages and 64 rings of 9 pages, keys of 192 stored 256
    wide, 16 of 256 experts held. Every program aliases all four pool
    arrays and holds no copy of one: with keys stored 192 wide the chip's
    compiler gave both K pools another layout inside the program and copied
    each twice a run. The grouped expert matmul is a kernel that reads a
    run's stack of expert weights where it lies: no operation yields a
    layer's ``[16, 4096, 2048]`` (three of 256 MiB a layer of the five-layer
    run, every step, call and chunk, when a scan sliced the stack). In
    ``decode_step`` it is Megablox ``gmm`` and no ``lax.ragged_dot`` is
    left; the prefill programs keep ``ragged_dot``."""
    d = deployment("mixed")
    eng, table = d.eng, d.tables["full"].shape[1]
    assert (eng.max_prefill_tokens, eng.prefill_chunk) == (2048, 512)
    assert (eng.pool.ring_pages, eng.pool.k_dim) == (9, 256)
    if program == "decode_step":
        compiled = d.decode_step()
    elif program == "prefill":
        compiled = eng._prefill.lower(
            d.params, d.pool_k, d.pool_v, d.ints(2048), 2048,
            {"full": d.ints(2048 // PAGE), "window": d.ints(9)},
            d.state, d.ints(), d.ints(),
        ).compile()
    else:
        compiled = eng._prefill_suffix.lower(
            d.params, d.pool_k, d.pool_v, d.ints(512), 512, d.ints(),
            {"full": d.ints(table), "window": d.ints(9)},
            d.ints(512 // PAGE), d.state, d.ints(), d.ints(),
        ).compile()
    text = compiled.as_text()
    assert not d.copies_of_a_pool(text)
    assert not d.copies_of_experts(text)
    gmm, ragged = d.grouped_matmuls(text)
    if program == "decode_step":
        # gate, up and down of both expert runs (five windowed layers, one
        # full), in both branches of the row budget (64 and 512 rows)
        assert (gmm, ragged) == (3 * 2 * 2, 0)
    else:  # prefill keeps lax.ragged_dot
        assert gmm == 0 and ragged
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == sum(2 * p.size for p in d.pools)
    # temporaries: the rings' gathered tables of 64 slots; the first
    # program's [64, 2048, 2048] scores (1.02-1.03 GiB); a chunk walks its
    # table block by block (1.02 GiB of float32 scores over all of its 8,192
    # keys until PR 41; the decode step's 0.76 GiB were the three copied
    # matrices)
    bound = {"decode_step": 0.25, "prefill": 1.25, "prefill_suffix": 0.25}
    assert mem.temp_size_in_bytes < bound[program] * 2**30
    if program == "prefill_suffix":
        assert not re.findall(r"f32\[[\d,]*\b8192\]", text)


# -- state by slot beside the paged KV at the widths of `lfm2-8b-a1b-l14` --------


@pytest.mark.parametrize("program", ["decode_step", "prefill", "prefill_suffix"])
def test_state_by_slot_and_heads_of_64_copy_no_pool_and_no_weights(
    deployment, program
):
    """``lfm2-8b-a1b-l14.turns``: 64 slots, contexts to 4,096 (a table of
    256), 8,192 pages of 3 attention layers x 8 KV heads x 64, and 11
    convolution layers' two columns of 2,048 a slot. Heads of 64 are stored
    128 wide: as they are, Mosaic refuses the kernel's DMA of a page
    ("slice shape along dimension 4 must be aligned to tiling (128), but is
    64"; the chip lays such a row out in 128 lanes either way). Every
    program aliases both pools and the state and copies none of them; the
    decode step holds the paged-attention kernel and Megablox ``gmm`` for
    every expert product, and no ``lax.ragged_dot``, which the prefill
    programs keep; and no run's and no layer's expert weights are
    copied: each run
    has a stack of its own, where a static slice of its kind's stack was a
    copy of ``[3, 32, 2048, 1792]`` a matrix a step, and the grouped matmul
    reads the stack in place, where the scan's slice of a layer was nine
    copies of ``[32, 2048, 1792]`` a step (a regex that wanted a leading
    ``[1,`` or ``[3,`` passed over them until PR 39)."""
    d = deployment("lfm2")
    eng, table = d.eng, d.tables["full"].shape[1]
    assert (eng.pool.k_dim, eng.pool.v_dim, table) == (128, 128, 256)
    assert eng.max_prefill_tokens == 2896  # the cell's 2,048 in one program
    assert d.state["conv"].shape == (11, 2, 64, 2048)
    assert [r.count for r in d.cfg.layer_runs()] == [2, 1, 3, 1, 3, 1, 3]
    if program == "decode_step":
        compiled = d.decode_step()
    elif program == "prefill":
        compiled = eng._prefill.lower(
            d.params, d.pool_k, d.pool_v, d.ints(2048), 2048,
            {"full": d.ints(2048 // PAGE)}, d.state, d.ints(), d.ints(),
        ).compile()
    else:  # no prompt of the cell takes it; a longer one would
        compiled = eng._prefill_suffix.lower(
            d.params, d.pool_k, d.pool_v, d.ints(720), 720, d.ints(),
            {"full": d.ints(table)}, d.ints(720 // PAGE), d.state,
            d.ints(), d.ints(),
        ).compile()
    text = compiled.as_text()
    assert not d.copies_of_a_pool(text)
    assert not re.findall(r"= bf16\[11,2,64,2048\]\S* copy\(", text)
    assert not d.copies_of_experts(text)
    gmm, ragged = d.grouped_matmuls(text)
    if program == "decode_step":
        assert "paged_attention_decode" in text
        # gate, up and down of the six expert runs (1 + 3 layers, three
        # times); every pair fits the one row budget
        assert (gmm, ragged) == (3 * 6, 0)
    else:  # prefill keeps lax.ragged_dot
        assert gmm == 0 and ragged
    mem = compiled.memory_analysis()
    state = 2 * d.state["conv"].size
    assert mem.alias_size_in_bytes == sum(2 * p.size for p in d.pools) + state
    # what a program adds beside its operands, temporaries and results of its
    # own: 0.23, 0.06 and 0.68 GiB of temporaries when the stacks were by
    # kind, the copied matrices among them. A prefill program returns one
    # row of logits, and the compiler keeps in temporaries what it kept in
    # the [2048, 65536] float32 logits' buffer before (0.035 GiB of
    # temporaries beside 0.5 of logits then; 0.54 and 0.0002 now, the
    # prefill's expert rows among them)
    bound = {"decode_step": 0.05, "prefill": 0.6, "prefill_suffix": 0.5}
    own = mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert mem.temp_size_in_bytes + own < bound[program] * 2**30


# -- a float32 matrix of state a head at the widths of `olmo-hybrid-7b-l16` ------


@pytest.mark.parametrize("program", ["decode_step", "prefill", "prefill_suffix"])
def test_a_matrix_of_state_a_head_and_groups_of_one_copy_no_state(
    deployment, program
):
    """``olmo-hybrid-7b-l16.longdoc``: 16 slots, contexts to 16,896 (a
    table of 1,056 pages, twice the longest before it), 4,096 pages of 4
    full layers x 30 KV heads x 128 with as many query heads (the kernel's
    groups of one: a chunk is 17 pages of 240 KiB), and 12 delta layers'
    state by slot: ``S`` ``[12, 16, 30, 96, 192]`` in float32 and the
    columns ``[12, 3, 16, 11520]``. The chip lays a row of 192 out in 256
    lanes, so ``S`` takes 4/3 of its 0.40 GiB; it keeps that one layout
    through every program. Every program aliases both pools and both
    arrays of state and copies none of them: ``decode_step`` updates the
    whole state in place inside the layers' scans, the prefill programs
    carry one slot's rows and put them in once. The first prefill program
    takes 2,976 tokens and a chunk 736 (30 heads), neither a multiple of
    the scan's block of 64: the last block is ragged. A chunk walks its
    table block by block: no float32 scores over its 16,896 keys (1.39 of
    1.73 GiB of temporaries until PR 41) are left."""
    d = deployment("olmo")
    eng, table = d.eng, d.tables["full"].shape[1]
    assert (eng.pool.k_dim, eng.pool.v_dim, table) == (128, 128, 1056)
    assert (eng.max_prefill_tokens, eng.prefill_chunk) == (2976, 736)
    assert d.state["delta_s"].shape == (12, 16, 30, 96, 192)
    assert d.state["delta_s"].dtype == jnp.float32
    assert d.state["delta_taps"].shape == (12, 3, 16, 11520)
    assert [r.count for r in d.cfg.layer_runs()] == [3, 1] * 4
    if program == "decode_step":
        compiled = d.decode_step()
    elif program == "prefill":
        compiled = eng._prefill.lower(
            d.params, d.pool_k, d.pool_v, d.ints(2976), 2976,
            {"full": d.ints(2976 // PAGE)}, d.state, d.ints(), d.ints(),
        ).compile()
    else:
        compiled = eng._prefill_suffix.lower(
            d.params, d.pool_k, d.pool_v, d.ints(736), 736, d.ints(),
            {"full": d.ints(table)}, d.ints(736 // PAGE), d.state,
            d.ints(), d.ints(),
        ).compile()
    text = compiled.as_text()
    assert not d.copies_of_a_pool(text)
    assert not re.findall(r"= f32\[12,16,30,96,192\]\S* copy\(", text)
    assert not re.findall(r"= bf16\[12,3,16,11520\]\S* copy\(", text)
    # the layouts arrays have (a kernel's operand_layout_constraints name
    # its operands' shapes without their tiles)
    held = re.sub(r"operand_layout_constraints=\{.*?\}\}", "", text)
    assert set(re.findall(r"f32\[12,16,30,96,192\](\{[^}]*\})", held)) == {
        "{4,3,2,1,0:T(8,128)}"}
    if program == "decode_step":
        kernels = [
            line for line in text.splitlines()
            if f'custom_call_target="{KERNEL}"' in line
            and "paged_attention_decode" in line
        ]
        assert kernels and all("bf16[4,30,4096,16,128]" in k for k in kernels)
        # the delta rule in the state kernel, one call in each of the four
        # runs' scans (a call a layer), its result the state operand's
        # buffer; no fusion is handed the whole state beside it
        whole = "f32[12,16,30,96,192]"
        assert d.state_kernels(text) == [(whole, whole)] * 4
        assert not d.readers_of_whole(text, "12,16,30,96,192")
        assert "select_dynamic-update-slice" not in text
    mem = compiled.memory_analysis()
    # as the chip stores it: rows of 192 in 256 lanes
    state = 12 * 16 * 30 * 96 * 256 * 4 + 2 * d.state["delta_taps"].size
    assert mem.alias_size_in_bytes == sum(2 * p.size for p in d.pools) + state
    # what a program adds beside its operands, temporaries and results of
    # its own: the first program 0.55 GiB of temporaries beside 1.11 of
    # logits when it returned every row, 1.35 and 0.0004 with one (the compiler keeps in
    # temporaries what it kept in the logits' buffer); a chunk 0.16 and
    # 0.27, then 0.18 and 0.0004
    own = mem.output_size_in_bytes - mem.alias_size_in_bytes
    bound = {"decode_step": 0.05, "prefill": 1.5, "prefill_suffix": 0.3}
    assert mem.temp_size_in_bytes + own < bound[program] * 2**30
    if program == "prefill_suffix":
        assert not re.findall(r"f32\[[\d,]*\b16896\]", text)  # [736,30,1,16896]
    # nothing but the row of logits the host reads leaves a prefill program
    # beside what it aliases (every row of the run's was 1.1 GiB at 2,976
    # tokens)
    assert own < 100352 * 4 + 2**20


# -- a parallel layer's pages and state at the widths of `falcon-h1-34b-l6` ------


@pytest.mark.parametrize("program", ["decode_step", "prefill", "prefill_suffix"])
def test_a_parallel_layer_writes_pages_and_state_and_copies_neither(
    deployment, program
):
    """``falcon-h1-34b-l6.think``: 64 slots, contexts to 4,096 (a table of
    256 pages), 12,288 pages of 6 layers x 4 KV heads x 128 read by groups
    of 5 query heads (the first group neither a power of two nor a multiple
    of 8 the kernel meets), and in the same 6 layers the Mamba-2 mixer's
    state by slot: ``S`` ``[6, 64, 32, 256, 128]`` in float32 (1.5 GiB) and
    the columns ``[6, 3, 64, 5120]``. Every program aliases both pools and
    both arrays of state and copies none of them: ``decode_step`` moves
    every live slot's state on in place inside the layers' scan, the prefill
    programs carry one slot's rows and put them in once. The first prefill
    program takes the cell's longest prompt, 2,048 tokens (it could take
    3,648 at 20 heads), a chunk 912. Each prefill program returns one row
    of logits: over every row of a 2,048-token prompt a head of 261,120
    would be 2 GiB of float32 beside 9.8 GiB of weights."""
    d = deployment("falcon")
    eng, table = d.eng, d.tables["full"].shape[1]
    assert (eng.pool.k_dim, eng.pool.v_dim, table) == (128, 128, 256)
    assert (eng.max_prefill_tokens, eng.prefill_chunk) == (3648, 912)
    assert d.state["ssm_s"].shape == (6, 64, 32, 256, 128)
    assert d.state["ssm_s"].dtype == jnp.float32
    assert d.state["ssm_taps"].shape == (6, 3, 64, 5120)
    assert d.state["ssm_taps"].dtype == jnp.bfloat16
    assert [r.count for r in d.cfg.layer_runs()] == [6]
    if program == "decode_step":
        compiled = d.decode_step()
    elif program == "prefill":
        compiled = eng._prefill.lower(
            d.params, d.pool_k, d.pool_v, d.ints(2048), 2048,
            {"full": d.ints(2048 // PAGE)}, d.state, d.ints(), d.ints(),
        ).compile()
    else:
        compiled = eng._prefill_suffix.lower(
            d.params, d.pool_k, d.pool_v, d.ints(912), 912, d.ints(),
            {"full": d.ints(table)}, d.ints(912 // PAGE), d.state,
            d.ints(), d.ints(),
        ).compile()
    text = compiled.as_text()
    assert not d.copies_of_a_pool(text)
    # nothing yields the whole state but the program's own operand and
    # result (a copy, or an update that is not in place); the state
    # kernel's pair of results holds it, aliased
    for dims in (r"f32\[6,64,32,256,128\]", r"bf16\[6,3,64,5120\]"):
        made = re.findall(
            rf"(\S+) = {dims}\S* (?!bitcast\(|get-tuple-element\(|parameter\()"
            r"([\w-]+)\(", text)
        made += re.findall(rf"(\S+) = \([^=]*?{dims}[^=]*?\) ([\w-]+)\(", text)
        assert all(
            op in ("dynamic-update-slice", "fusion", "tuple", "custom-call")
            for _, op in made
        ), made
        assert not re.findall(rf"= {dims}\S* copy\(", text)
    if program == "decode_step":
        kernels = [
            line for line in text.splitlines()
            if f'custom_call_target="{KERNEL}"' in line
            and "paged_attention_decode" in line
        ]
        assert kernels and all("bf16[6,4,12288,16,128]" in k for k in kernels)
        assert "f32[64,4,5,128]" in text  # the kernel's groups of 5
        # the Mamba-2 state in the state kernel, one call in the run's scan
        # (a call a layer), its result the state operand's buffer; no
        # fusion is handed the whole state beside it (the parent's readout
        # and select-and-update, 2.71 s of 8.5 busy on the chip)
        whole = "f32[6,64,32,256,128]"
        assert d.state_kernels(text) == [(whole, whole)]
        assert not d.readers_of_whole(text, "6,64,32,256,128")
        assert "select_dynamic-update-slice" not in text
    mem = compiled.memory_analysis()
    state = 4 * d.state["ssm_s"].size + 2 * d.state["ssm_taps"].size
    assert mem.alias_size_in_bytes == sum(2 * p.size for p in d.pools) + state
    # compiled here: 5 MiB, 0.41 GiB (the [20, 2048, 2048] float32 scores
    # among them) and 0.12 GiB
    bound = {"decode_step": 0.05, "prefill": 0.75, "prefill_suffix": 0.25}
    assert mem.temp_size_in_bytes < bound[program] * 2**30
    own = mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert own < 261120 * 4 + 2**20


@pytest.mark.parametrize("name", ["mistral", "internlm2", "mixed", "lfm2"])
def test_a_decode_step_with_no_matrix_of_state_holds_no_state_kernel(
    deployment, name
):
    """The four configurations that keep no float32 matrix a head by slot
    (chat and rag share mistral's; ``turns``' state is columns) compile no
    state kernel into ``decode_step``, whichever the engine would choose."""
    d = deployment(name)
    assert d.eng._state_kernel == "compiled"
    text = d.decode_step().as_text()
    assert "state_step_decode" not in text and not d.state_kernels(text)


@pytest.mark.parametrize("name", ["mistral", "internlm2", "olmo", "falcon"])
def test_a_dense_decode_step_holds_no_grouped_matmul(deployment, name):
    """The four dense cells' ``decode_step`` (chat and rag share mistral's)
    has no expert layer: neither Megablox ``gmm`` nor ``lax.ragged_dot``
    is in it, whichever the engine would choose."""
    d = deployment(name)
    assert d.eng._moe_kernel == "compiled"
    assert d.grouped_matmuls(d.decode_step().as_text()) == (0, 0)


@pytest.mark.parametrize("name", ["mistral", "mixed", "lfm2"])
def test_a_scan_takes_every_weight_but_an_expert_runs_three_stacks(
    deployment, name
):
    """What ``run_stack`` hands each run's ``lax.scan``: a dense
    deployment's scan slices every stacked weight a layer at a time, as it
    did before an expert run's three large stacks were kept out; those are
    closed over whole (a run of one layer's too: the same code), and the
    router and its bias, which are small, stay in the scan."""
    d = deployment(name)
    sliced, whole = d.scans_of_decode_step()
    blocks = d.params["blocks"]
    for run in d.cfg.layer_runs():
        stack = dict(blocks if run.key is None else blocks[run.key])
        experts = stack.pop("moe", {})
        assert bool(experts) == run.experts
        for k, w in experts.items():
            if k.startswith("w_"):
                assert w.shape in whole and w.shape not in sliced, k
            else:
                assert w.shape in sliced, k
        for w in jax.tree.leaves(stack):
            assert w.shape in sliced and w.shape not in whole


# -- scheduler kernels: the head's real round ---------------------------------

N, R, B, U = 1024, 16, 4096, 8  # nodes, resources, sched_max_batch, shapes


def _cluster(sharding):
    return _shapes(
        sharding,
        ((N, R), jnp.float32),   # totals
        ((N, R), jnp.float32),   # avail
        ((N,), jnp.bool_),       # alive
        ((N,), jnp.int32),       # node types
        ((1, R), jnp.float32),   # per-type throughput
    )


@pytest.mark.parametrize("preempt", [False, True], ids=["plain", "preempt"])
def test_waterfall_round(one_chip, preempt):
    """The jitted round exactly as DeviceSchedulerState dispatches it."""
    from ray_tpu.scheduler.device import _jitted_fns, score_weights_from_cfg

    kernel = _jitted_fns()[0]
    demand = _shapes(
        one_chip,
        ((U, R), jnp.float32),
        ((B,), jnp.int32),
        ((U,), jnp.float32),
        ((), jnp.uint32),
    )
    kernel.lower(
        *_cluster(one_chip), *demand,
        spread_threshold=0.5, weights=score_weights_from_cfg(),
        preempt=preempt, explain=False,
    ).compile()


def test_ring_round(one_chip):
    from ray_tpu.scheduler.device import _jitted_fns, score_weights_from_cfg

    slots = 64  # sched_ring_slots
    ring = _shapes(
        one_chip,
        ((slots, R), jnp.float32),
        ((slots,), jnp.int32),
        ((slots,), jnp.float32),
        ((), jnp.uint32),
    )
    _jitted_fns()[2].lower(
        *_cluster(one_chip), *ring,
        spread_threshold=0.5, weights=score_weights_from_cfg(), preempt=True,
    ).compile()


def test_shape_slots(one_chip):
    from ray_tpu.scheduler.device import _jitted_fns

    totals, avail, alive = _cluster(one_chip)[:3]
    (shapes,) = _shapes(one_chip, ((64, R), jnp.float32))
    _jitted_fns()[3].lower(totals, avail, alive, shapes).compile()


def test_elasticity_solve(one_chip):
    """``elastic_pack_solve`` at chip_smoke's 8,192 nodes x 1,024 shapes."""
    from ray_tpu.scheduler.binpack import solve_pack_counts

    args = _shapes(
        one_chip,
        ((8192, R), jnp.float32),
        ((1024, R), jnp.float32),
        ((1024,), jnp.float32),
    )
    solve_pack_counts.lower(*args, iters=24).compile()


@pytest.mark.parametrize("strategy", ["PACK", "SPREAD", "STRICT_SPREAD"])
def test_bundle_kernels(one_chip, strategy):
    from ray_tpu.scheduler import bundles

    totals, avail, alive = _cluster(one_chip)[:3]
    (mat,) = _shapes(one_chip, ((8, R), jnp.float32))
    if strategy == "PACK":
        bundles.pack_bundles.lower(totals, avail, alive, mat).compile()
    else:
        bundles.spread_bundles.lower(
            totals, avail, alive, mat, strict=strategy == "STRICT_SPREAD"
        ).compile()


# -- the train step under a mesh ----------------------------------------------


@pytest.mark.parametrize("mesh_name", ["dp2_tp2", "pp2_tp2"])
def test_train_step_keeps_the_flash_kernels_under_a_mesh(
    topo, mesh_name, monkeypatch
):
    """A Mosaic kernel cannot be split by the partitioner, and autodiff
    cannot carry residuals out of a shard_map nested in the pipeline's:
    ``transformer._causal_attention`` spells both out, and
    ``make_train_step`` pins what the step returns. Small widths — the
    structure is what is compiled here; chip_smoke.py --multichip runs it
    at full width."""
    import optax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import MeshConfig, build_mesh

    # the model asks the attached backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mc = {
        "dp2_tp2": MeshConfig(dp=2, tp=2),
        "pp2_tp2": MeshConfig(pp=2, tp=2),
    }[mesh_name]
    mesh = build_mesh(mc, topo.devices)
    cfg = tfm.ModelConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=512, max_seq_len=256, remat=True,
    )
    opt = optax.adam(3e-4)

    def on_mesh(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)
            ),
            tree,
            specs,
        )

    specs = tfm.param_specs(cfg, mc.pp)
    params = on_mesh(
        jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))),
        specs,
    )
    adam, rest = jax.eval_shape(opt.init, params)
    opt_state = (
        adam._replace(
            count=on_mesh(adam.count, jax.sharding.PartitionSpec()),
            mu=on_mesh(adam.mu, specs),
            nu=on_mesh(adam.nu, specs),
        ),
        rest,
    )
    tokens = jax.ShapeDtypeStruct(
        (4, 256), jnp.int32,
        sharding=NamedSharding(mesh, jax.sharding.PartitionSpec("dp", None)),
    )
    step = jax.jit(
        tfm.make_train_step(
            cfg, opt, mesh, num_microbatches=2 * mc.pp if mc.pp > 1 else 0
        )
    )
    compiled = step.lower(params, opt_state, tokens).compile()
    # forward, its remat copy, dq, dk/dv
    assert compiled.as_text().count(f'"{KERNEL}"') >= 3
    # parameters and optimizer state leave the step sharded as they
    # entered (left open, this compiler splits the norm scales over tp)
    out_params, out_state, _ = compiled.output_shardings
    for want, got in zip(
        jax.tree.leaves((params, opt_state)),
        jax.tree.leaves((out_params, out_state)),
    ):
        assert got.is_equivalent_to(want.sharding, len(want.shape))
