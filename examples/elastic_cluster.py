"""A zero-node cluster that scales itself.

Start a head with NO worker nodes, submit work, and let the autoscaler +
LocalNodeProvider launch real agent subprocesses to run it; idle nodes
terminate afterwards. Run: PYTHONPATH=. python examples/elastic_cluster.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ray_tpu  # noqa: E402
from ray_tpu.autoscaler import (  # noqa: E402
    Autoscaler,
    InstanceManager,
    LocalNodeProvider,
    NodeTypeConfig,
)
from ray_tpu.cluster import Cluster  # noqa: E402
from ray_tpu.core.runtime import set_runtime  # noqa: E402


def main():
    c = Cluster()  # head only — zero nodes
    client = c.client()
    set_runtime(client)
    provider = InstanceManager(LocalNodeProvider(c.address, num_workers=2))
    scaler = Autoscaler(
        client,
        [NodeTypeConfig("cpu4", {"CPU": 4.0}, max_workers=3)],
        provider=provider,
        idle_timeout_s=3.0,
    )
    try:
        scaler.start()  # reconcile loop: launch on demand, reap idle
        f = ray_tpu.remote(lambda x: x * x).options(num_cpus=1.0)
        refs = [f.remote(i) for i in range(8)]
        print("results:", ray_tpu.get(refs, timeout=180))
        for _ in range(30):
            alive = [
                n for n in provider.non_terminated_nodes() if n["Alive"]
            ]
            if provider.summary().get("TERMINATED", 0) and not alive:
                break
            time.sleep(1.0)
        print("instances after idle scale-down:", provider.summary())
    finally:
        scaler.stop()
        set_runtime(None)
        client.shutdown()
        provider.shutdown()
        c.shutdown()


if __name__ == "__main__":
    main()
