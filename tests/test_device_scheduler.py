"""The device-resident XLA scheduler as the live runtime's default path.

The kernels must be the product scheduler, state resident on the scheduler
device with delta sync, and no prefer-row hotspot.
"""
import numpy as np
import pytest

import ray_tpu
from ray_tpu.scheduler.device import DeviceSchedulerState
from ray_tpu.scheduler.resources import ClusterView, ResourceVocab


def make_view(n_nodes=4, cpu=8.0):
    vocab = ResourceVocab()
    view = ClusterView(vocab)
    for i in range(n_nodes):
        view.add_node(f"node{i}", {"CPU": cpu, "memory": 1e9})
    return vocab, view


def dense(vocab, view, res):
    from ray_tpu.scheduler.resources import ResourceRequest

    return ResourceRequest.from_map(vocab, res).dense(view.totals.shape[1])


def test_default_on_in_runtime_and_head():
    rt = ray_tpu.init(num_nodes=2, resources_per_node={"CPU": 2.0})
    try:
        assert rt.device_state is not None
        assert rt.use_device_scheduler
    finally:
        ray_tpu.shutdown()
    from ray_tpu.cluster.head import HeadServer

    head = HeadServer()
    try:
        assert head.device_state is not None
    finally:
        head.shutdown()


def test_schedule_and_delta_sync():
    vocab, view = make_view(2, cpu=4.0)
    st = DeviceSchedulerState()
    view_lockless_sync = st.sync
    view_lockless_sync(view)
    d = dense(vocab, view, {"CPU": 4.0})
    rows = st.schedule(np.stack([d, d]))
    assert sorted(rows.tolist()) == [0, 1]  # one per node, capacity-exact

    # host reports node0 free again (agent report analog) → dirty-row push
    view.update_available("node0", {"CPU": 4.0, "memory": 1e9})
    assert view.dirty_rows
    st.sync(view)
    assert not view.dirty_rows
    rows = st.schedule(np.stack([d]))
    assert rows.tolist() == [0]
    # node0 is consumed on-device again; nothing fits now
    rows = st.schedule(np.stack([d]))
    assert rows.tolist() == [-1]


def test_full_resync_on_topology_change():
    vocab, view = make_view(1, cpu=2.0)
    st = DeviceSchedulerState()
    st.sync(view)
    d = dense(vocab, view, {"CPU": 2.0})
    assert st.schedule(np.stack([d])).tolist() == [0]
    view.subtract(0, d)  # the optimistic host-mirror deduction callers make
    # new node joins → topo bump → full re-upload (from the host mirror)
    view.add_node("nodeX", {"CPU": 2.0, "memory": 1e9})
    st.sync(view)
    d = dense(vocab, view, {"CPU": 2.0})
    assert st.schedule(np.stack([d])).tolist() == [1]


def test_no_node_zero_hotspot():
    """weak-5 regression: with all nodes idle (sub-threshold scores), small
    batches must not all land on row 0 — the shapes kernel has no prefer row
    and jitters ties."""
    vocab, view = make_view(8, cpu=64.0)
    st = DeviceSchedulerState()
    st.sync(view)
    d = dense(vocab, view, {"CPU": 1.0})
    counts = np.zeros(8, dtype=int)
    # many single-request rounds — the pathological case
    for _ in range(48):
        row = int(st.schedule(np.stack([d]))[0])
        counts[row] += 1
    assert counts[0] < 24, f"node-0 hotspot: {counts.tolist()}"
    assert (counts > 0).sum() >= 4, f"no spread: {counts.tolist()}"


def test_infeasible_and_unknown_resource_park():
    rt = ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 1.0})
    try:
        f = ray_tpu.remote(lambda: 1).options(resources={"no_such_res": 1.0})
        ref = f.remote()
        with pytest.raises(TimeoutError):
            ray_tpu.get(ref, timeout=0.5)
        # becomes schedulable once a node with that resource appears
        rt.add_node({"CPU": 1.0, "no_such_res": 2.0})
        assert ray_tpu.get(ref, timeout=30) == 1
    finally:
        ray_tpu.shutdown()


def test_device_matches_golden_capacity():
    """The device path must place exactly what fits (capacity exactness the
    NumPy golden model guarantees)."""
    vocab, view = make_view(3, cpu=2.0)
    st = DeviceSchedulerState()
    st.sync(view)
    d = dense(vocab, view, {"CPU": 1.0})
    rows = st.schedule(np.stack([d] * 10))
    placed = rows[rows >= 0]
    assert placed.shape[0] == 6  # 3 nodes x 2 CPU
    binc = np.bincount(placed, minlength=3)
    assert binc.max() <= 2


# ---------------------------------------------------------------------------
# no fallback hides the device
# ---------------------------------------------------------------------------


def test_named_platform_that_is_absent_is_an_error(monkeypatch):
    """RAY_TPU_SCHED_PLATFORM=tpu where there is no TPU: the scheduler
    raises, every time; it does not move to the CPU or the host model."""
    from ray_tpu.scheduler.device import LazyDeviceState

    with pytest.raises(RuntimeError):
        DeviceSchedulerState(platform="tpu")
    monkeypatch.setenv("RAY_TPU_SCHED_PLATFORM", "tpu")
    lazy = LazyDeviceState(True)
    with pytest.raises(RuntimeError) as first:
        lazy.get()
    with pytest.raises(RuntimeError) as again:
        lazy.get()
    assert again.value is first.value and lazy._result is None
    # off means off: the NumPy golden model, by the caller's choice
    assert LazyDeviceState(False).get() is None


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_is_placed_from_outside(from_env, tmp_path):
    """JAX_COMPILATION_CACHE_DIR decides; unset, the cache is one fixed
    directory inside the checkout. A fresh process: the helper runs once."""
    import os
    import subprocess
    import sys

    from ray_tpu.util import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [
            sys.executable, "-c",
            "import jax\n"
            "from ray_tpu.scheduler.device import DeviceSchedulerState\n"
            "DeviceSchedulerState()\n"
            "print(jax.config.jax_compilation_cache_dir)\n",
        ],
        capture_output=True, text=True, timeout=120, cwd=repo, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    want = str(tmp_path) if from_env else compile_cache.DEFAULT_DIR
    assert out.stdout.strip().splitlines()[-1] == want
    assert compile_cache.DEFAULT_DIR == os.path.join(
        repo, ".jax_compile_cache"
    )
