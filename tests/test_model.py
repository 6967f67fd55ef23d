"""Flagship model tests: numerics parity across parallelism modes on the
8-device virtual CPU mesh (conftest sets the flags)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.parallel import MeshConfig, build_mesh

CFG = tfm.ModelConfig(
    vocab_size=128,
    d_model=32,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    d_ff=64,
    max_seq_len=64,
    dtype=jnp.float32,  # exact comparisons on CPU
)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(CFG, key)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, CFG.vocab_size)
    logits = tfm.forward(params, tokens, CFG)
    return params, tokens, logits


def test_forward_shapes(setup):
    params, tokens, logits = setup
    assert logits.shape == (4, 17, CFG.vocab_size)
    assert jnp.isfinite(logits).all()


def test_causality(setup):
    params, tokens, logits = setup
    # Perturbing a later token must not change earlier logits.
    tokens2 = tokens.at[:, 10].set((tokens[:, 10] + 1) % CFG.vocab_size)
    logits2 = tfm.forward(params, tokens2, CFG)
    np.testing.assert_allclose(
        np.asarray(logits[:, :10]), np.asarray(logits2[:, :10]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits[:, 10:]), np.asarray(logits2[:, 10:]))


def test_sp_ring_attention_matches_dense(setup):
    params, tokens, _ = setup
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, CFG.vocab_size)
    dense = tfm.forward(params, toks, CFG)
    mesh = build_mesh(MeshConfig(sp=4), jax.devices()[:4])
    ring = tfm.forward(params, toks, CFG, mesh)
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(ring), atol=2e-4, rtol=2e-4
    )


def test_sp_ulysses_attention_matches_dense(setup):
    params, tokens, _ = setup
    import dataclasses

    cfg = dataclasses.replace(CFG, sp_attention="ulysses")
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0, CFG.vocab_size)
    dense = tfm.forward(params, toks, cfg)
    mesh = build_mesh(MeshConfig(sp=4), jax.devices()[:4])
    out = tfm.forward(params, toks, cfg, mesh)
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(out), atol=2e-4, rtol=2e-4
    )


def test_ulysses_raw_matches_reference(devices8):
    """ulysses_attention under shard_map vs dense reference attention,
    incl. the GQA head-replication path (hkv < sp)."""
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from ray_tpu.ops.ulysses import ulysses_attention
    from ray_tpu.models.transformer import attention_reference

    b, t, h, hkv, d = 2, 32, 8, 2, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, t, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, hkv, d), jnp.float32)
    mesh = Mesh(np.array(devices8[:4]), ("sp",))
    fn = shard_map(
        partial(ulysses_attention, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = jax.jit(fn)(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


def test_pp_pipeline_matches_dense(setup):
    params, tokens, _ = setup
    toks = jax.random.randint(jax.random.PRNGKey(3), (8, 12), 0, CFG.vocab_size)
    dense = tfm.forward(params, toks, CFG)
    mesh = build_mesh(MeshConfig(pp=2), jax.devices()[:2])
    piped = tfm.forward(params, toks, CFG, mesh, num_microbatches=4)
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(piped), atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize(
    "mesh_cfg",
    [MeshConfig(dp=2, tp=2), MeshConfig(pp=2, tp=2)],
    ids=["dp2_tp2", "pp2_tp2"],
)
def test_tp_meshes_match_one_device_loss_and_grads(setup, devices8, mesh_cfg):
    """The two meshes chip_smoke.py --multichip takes to four chips: the
    loss and every gradient equal the unsharded ones."""
    params, _, _ = setup
    toks = jax.random.randint(jax.random.PRNGKey(5), (8, 17), 0, CFG.vocab_size)
    want_l, want_g = jax.value_and_grad(tfm.loss_fn)(params, toks, CFG)
    mesh = build_mesh(mesh_cfg, devices8[:4])
    mbs = 2 * mesh_cfg.pp if mesh_cfg.pp > 1 else 0
    got_l, got_g = jax.jit(
        jax.value_and_grad(
            lambda p, t: tfm.loss_fn(p, t, CFG, mesh, num_microbatches=mbs)
        )
    )(tfm.shard_params(params, CFG, mesh), toks)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-5)
    for got, want in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-4
        )


def test_full_mesh_train_step_runs_and_matches(devices8):
    mesh = build_mesh(MeshConfig(dp=2, pp=2, sp=2), devices8)
    params = tfm.init_params(CFG, jax.random.PRNGKey(0))
    params = tfm.shard_params(params, CFG, mesh)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (8, 17), 0, CFG.vocab_size)
    step = jax.jit(tfm.make_train_step(CFG, opt, mesh, num_microbatches=2))
    p2, s2, loss = step(params, opt_state, tokens)
    assert jnp.isfinite(loss)
    # one more step: loss should change (params updated)
    _, _, loss2 = step(p2, s2, tokens)
    assert float(loss2) != float(loss)
    assert float(loss2) < float(loss) + 1.0


def test_moe_model_runs():
    cfg = tfm.ModelConfig(
        vocab_size=64,
        d_model=16,
        n_layers=2,
        n_heads=2,
        n_kv_heads=2,
        d_ff=32,
        n_experts=4,
        dtype=jnp.float32,
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (2, 9, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    loss = tfm.loss_fn(params, tokens, cfg)
    assert jnp.isfinite(loss)
