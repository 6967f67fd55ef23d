"""Data-parallel training with the JaxTrainer worker gang.

The training loop runs on every rank (worker actor); ranks shard their
data, train a small linear model with optax, and report metrics through
the session API. Run: PYTHONPATH=. python examples/distributed_training.py
"""
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.train import JaxTrainer, ScalingConfig, get_context, report  # noqa: E402


def train_fn(config):
    import jax
    import jax.numpy as jnp
    import optax

    ctx = get_context()
    rank = ctx.get_world_rank()
    opt = optax.sgd(0.1)
    w = jnp.zeros((8, 1))
    state = opt.init(w)

    @jax.jit
    def step(w, state, x, y):
        def loss_fn(w):
            return jnp.mean((x @ w - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(w)
        up, state = opt.update(g, state)
        return optax.apply_updates(w, up), state, loss

    rng = np.random.default_rng(rank)
    true_w = np.arange(8, dtype=np.float32)[:, None]
    loss = None
    for epoch in range(config["epochs"]):
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = x @ true_w
        w, state, loss = step(w, state, jnp.asarray(x), jnp.asarray(y))
        report({"epoch": epoch, "loss": float(loss), "rank": rank})
    return {"final_loss": float(loss), "rank": rank}


def main():
    ray_tpu.init(num_nodes=2, resources_per_node={"CPU": 8})
    trainer = JaxTrainer(
        train_fn,
        train_loop_config={"epochs": 30},
        scaling_config=ScalingConfig(num_workers=2),
    )
    result = trainer.fit()
    print("result:", result.metrics)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
