"""Cross-node zero-copy transport: peer-leased data sockets + striping.

Covers the transport plane end to end: C-vs-Python framing parity over
fuzzed objects (non-contiguous numpy included), the peer-link lease
lifecycle (grant / reuse / renew / idle-TTL return / revoke-on-death),
the RAY_TPU_NATIVE_NET=0 kill switch's path equivalence, steady-state
transfers making zero head RPCs (handler-counter delta), head-restart
survival (granted links keep serving head-free, then re-fence on the
epoch bump), resume-mid-stripe under chaos severs with zero loss and no
duplicate bytes, and the fetch_chunked relocate fix (a dead source
aborts the pull instead of burning the retry budget).
"""
import os
import tempfile
import threading
import time

import numpy as np
import pytest

from ray_tpu.cluster import serialization as wire
from ray_tpu.cluster import transport as tp
from ray_tpu.native.shm_store import NativeObjectStore

OID_A = "a" * 28
OID_B = "b" * 28


def _wait_for(pred, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {msg}")


@pytest.fixture()
def arena():
    store = NativeObjectStore(
        path=os.path.join(
            tempfile.gettempdir(), f"t_net_{os.getpid()}_{time.time_ns()}.shm"
        ),
        capacity=1 << 27,
    )
    yield store
    store.close(unlink=True)


@pytest.fixture()
def served(arena):
    srv = tp.DataPlaneServer(arena, "nodesrv", "tok-secret", lambda: 100)
    link = tp.PeerLink(
        "lk0", "nodesrv", srv.endpoint, "tok-secret", 100, "nodecli"
    )
    yield arena, srv, link
    link.close()
    srv.close()


# ---------------------------------------------------------------------------
# framing parity + kill switch
# ---------------------------------------------------------------------------


def _fuzz_objects(rng):
    yield {"a": rng.standard_normal(300_000), "meta": {"k": [1, "x", None]}}
    yield rng.integers(0, 255, size=1 << 21, dtype=np.uint8)
    # non-contiguous: strided views pickle in-band (PickleBuffer raises)
    base = rng.standard_normal((512, 512))
    yield {"strided": base[::2, ::3], "t": (base[0], "s" * 10_000)}
    yield [b"x" * 70_000, bytearray(b"y" * 5), memoryview(b"z" * 4096)]
    yield {"empty": np.empty(0), "zero": b"", "n": 42}


@pytest.mark.parametrize("native", [True, False], ids=["c", "python"])
def test_socket_transfer_parity_fuzzed(served, monkeypatch, native):
    """The same fuzzed objects round-trip the socket byte-identically on
    the C sendmsg path and the Python socket fallback (the kill switch
    swaps implementations, never bytes)."""
    if not native:
        monkeypatch.setenv("RAY_TPU_NATIVE_NET", "0")
    store, srv, link = served
    rng = np.random.default_rng(7)
    for i, obj in enumerate(_fuzz_objects(rng)):
        oid = f"{i:028d}"
        parts, total = wire.dumps_parts(obj)
        store.put_frames(oid, parts)
        got = tp.fetch_bytes(link, oid)
        assert len(got) == total
        back = wire.loads(memoryview(got))
        _assert_equal_obj(back, obj)


def _assert_equal_obj(a, b):
    if isinstance(b, np.ndarray):
        assert np.array_equal(np.asarray(a), b)
    elif isinstance(b, dict):
        assert set(a) == set(b)
        for k in b:
            _assert_equal_obj(a[k], b[k])
    elif isinstance(b, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_equal_obj(x, y)
    elif isinstance(b, memoryview):
        assert bytes(a) == bytes(b)
    else:
        assert a == b


def test_striped_fetch_lands_in_arena_zero_copy(served, monkeypatch):
    """A multi-stripe transfer scatter-lands straight into a receiving
    arena (begin_put staging) and seals only once complete."""
    monkeypatch.setenv("RAY_TPU_NET_STRIPE_BYTES", str(1 << 20))
    monkeypatch.setenv("RAY_TPU_NET_STRIPE_CONNS", "3")
    store, srv, link = served
    payload = np.random.default_rng(1).integers(
        0, 255, size=10 << 20, dtype=np.uint8
    ).tobytes()
    store.put_bytes(OID_A, payload)
    dst = NativeObjectStore(
        path=os.path.join(
            tempfile.gettempdir(), f"t_netdst_{os.getpid()}.shm"
        ),
        capacity=1 << 26,
    )
    try:
        size = tp.fetch_to_store(link, OID_A, dst)
        assert size == len(payload)
        assert dst.get_bytes(OID_A) == payload
        assert srv.stats["stripes_served"] >= 10
    finally:
        dst.close(unlink=True)


def test_handshake_rejects_bad_token_and_stale_epoch(served):
    """Data-path fencing: a wrong token or a provably-stale epoch is
    refused at the handshake, before any byte of payload moves."""
    store, srv, link = served
    store.put_bytes(OID_B, b"q" * 128)
    bad = tp.PeerLink("lk1", "nodesrv", srv.endpoint, "WRONG", 100, "c")
    with pytest.raises(tp.LinkRejectedError) as ei:
        tp.fetch_bytes(bad, OID_B)
    assert ei.value.code == tp.HS_BAD_TOKEN
    stale = tp.PeerLink("lk2", "nodesrv", srv.endpoint, "tok-secret", 99, "c")
    with pytest.raises(tp.LinkRejectedError) as ei:
        tp.fetch_bytes(stale, OID_B)
    assert ei.value.code == tp.HS_STALE_EPOCH
    # unstamped (epoch 0) passes, mirroring FencedPayload semantics
    fresh = tp.PeerLink("lk3", "nodesrv", srv.endpoint, "tok-secret", 0, "c")
    assert bytes(tp.fetch_bytes(fresh, OID_B)) == b"q" * 128
    assert srv.stats["handshakes_rejected_token"] == 1
    assert srv.stats["handshakes_rejected_epoch"] == 1


def test_probe_survives_stale_pooled_connection(served):
    """A connection severed while POOLED (idle) must not degrade the
    next transfer to the RPC fallback: the probe redials once."""
    store, srv, link = served
    store.put_bytes(OID_B, b"p" * (1 << 16))
    assert bytes(tp.fetch_bytes(link, OID_B)) == b"p" * (1 << 16)
    srv.chaos_drop()  # shuts down the server end of the pooled connection
    assert bytes(tp.fetch_bytes(link, OID_B)) == b"p" * (1 << 16)
    # the serving thread counts a stripe after its send has returned
    _wait_for(lambda: srv.stats["stripes_served"] == 2, msg="second stripe")
    assert srv.stats["connections_accepted"] == 2  # one redial


@pytest.mark.parametrize("native", [True, False], ids=["c", "python"])
def test_sever_reaches_the_peer_while_the_server_waits(
    request, monkeypatch, native
):
    """A sever comes from a thread that does not own the connection, while
    the serving thread is blocked reading the next request. The pooled
    client end sees the end at once, without having to write first, and
    the serving thread is done with its descriptor: a close alone left
    both for the client's next request, and the number free for reuse
    under the serving thread's next read."""
    from ray_tpu.native.net import NetClosedError

    if not native:
        monkeypatch.setenv("RAY_TPU_NATIVE_NET", "0")
    store, srv, link = request.getfixturevalue("served")
    store.put_bytes(OID_B, b"s" * 1024)
    assert bytes(tp.fetch_bytes(link, OID_B)) == b"s" * 1024
    (pooled,) = link._idle
    assert pooled.native == native
    (serving,) = srv._conns.values()
    assert srv.chaos_drop() == 1
    pooled.set_timeout(10.0)
    with pytest.raises(NetClosedError):
        pooled.recv_exact(1)
    # a sever leaves the close to the owner, which has woken to do it
    _wait_for(lambda: serving.closed, msg="the serving thread to close")


def test_resume_mid_stripe_after_chaos_sever(served, monkeypatch):
    """peer_conn_drop semantics: severing the data sockets mid-striped-
    transfer re-fetches ONLY the lost stripes — the pull completes with
    zero loss and no duplicate bytes (content-exact)."""
    monkeypatch.setenv("RAY_TPU_NET_STRIPE_BYTES", str(1 << 20))
    monkeypatch.setenv("RAY_TPU_NET_STRIPE_CONNS", "2")
    store, srv, link = served
    payload = np.random.default_rng(3).integers(
        0, 255, size=24 << 20, dtype=np.uint8
    ).tobytes()
    store.put_bytes(OID_A, payload)
    got = {}

    def pull():
        got["data"] = tp.fetch_bytes(link, OID_A)

    t = threading.Thread(target=pull)
    t.start()
    # sever repeatedly while stripes are in flight
    for _ in range(3):
        time.sleep(0.02)
        srv.chaos_drop()
    t.join(timeout=60)
    assert not t.is_alive()
    assert bytes(got["data"]) == payload
    assert srv.stats["chaos_drops"] >= 1


# ---------------------------------------------------------------------------
# peer-link lease lifecycle against a real in-process head
# ---------------------------------------------------------------------------


@pytest.fixture()
def head(monkeypatch, tmp_path):
    from ray_tpu.cluster.head import HeadServer

    monkeypatch.setenv("RAY_TPU_HEALTH_TIMEOUT_S", "300")
    h = HeadServer(
        port=0,
        persist_path=str(tmp_path / "head_state.pkl"),
        use_device_scheduler=False,
    )
    yield h
    h.shutdown()


def _register_fake_node(head, node_id, endpoint="127.0.0.1:1", token="t0k"):
    from ray_tpu.cluster.common import NodeInfo

    return head._h_register_node(
        NodeInfo(
            node_id=node_id,
            address="127.0.0.1:1",
            resources={"CPU": 1.0},
            data_endpoint=endpoint,
            net_token=token,
        )
    )


def test_peer_link_grant_reuse_renew_return_revoke(head):
    from ray_tpu.cluster.rpc import RpcClient

    _register_fake_node(head, "nodeA", endpoint="127.0.0.1:7001")
    client = RpcClient(head.address)
    try:
        rep = client.call(
            "GrantPeerLink", {"src_node": "nodeB", "dst_node": "nodeA"}
        )
        assert rep["granted"] and rep["endpoint"] == "127.0.0.1:7001"
        assert rep["token"] == "t0k" and rep["epoch"] == head.cluster_epoch
        lid = rep["link_id"]
        # same-pair re-grant returns the SAME row (no duplicates)
        rep2 = client.call(
            "GrantPeerLink", {"src_node": "nodeB", "dst_node": "nodeA"}
        )
        assert rep2["link_id"] == lid
        assert head.metrics["peer_links_granted"] == 1
        # renewal pushes expiry out (the RPC drivers use, and the
        # piggyback path agents use, share _renew_peer_links)
        e = head._peer_links[lid]
        old_expiry = e["expires_at"]
        time.sleep(0.05)
        client.call("RenewPeerLinks", {"link_ids": [lid]})
        assert head._peer_links[lid]["expires_at"] > old_expiry
        # expiry sweep: force the horizon into the past -> revoked
        e["expires_at"] = time.monotonic() - 1.0
        head._expire_peer_links()
        assert lid not in head._peer_links
        assert head.metrics["peer_links_revoked"] == 1
        # grant again, then a clean ReturnPeerLink reclaims WITHOUT
        # counting as a revocation
        rep3 = client.call(
            "GrantPeerLink", {"src_node": "nodeB", "dst_node": "nodeA"}
        )
        client.call("ReturnPeerLink", {"link_id": rep3["link_id"]})
        assert rep3["link_id"] not in head._peer_links
        assert head.metrics["peer_links_revoked"] == 1
        # node death revokes links touching the node
        rep4 = client.call(
            "GrantPeerLink", {"src_node": "nodeB", "dst_node": "nodeA"}
        )
        head._on_node_death("nodeA")
        assert rep4["link_id"] not in head._peer_links
        assert head.metrics["peer_links_revoked"] == 2
        # and a dead destination refuses new grants
        rep5 = client.call(
            "GrantPeerLink", {"src_node": "nodeB", "dst_node": "nodeA"}
        )
        assert not rep5["granted"]
    finally:
        client.close()


def test_peer_link_cache_idle_ttl_and_reuse():
    """Requester-side cache: one grant per peer, cache hits bump the
    reuse counter, and idle links are swept + closed."""
    from ray_tpu.cluster.object_plane import PEER_CONN_REUSED

    grants = []

    def grant(node_id):
        link = tp.PeerLink(f"lk-{len(grants)}", node_id, "127.0.0.1:1", "t", 1)
        grants.append(link)
        return link

    cache = tp.PeerLinkCache(grant)
    before = PEER_CONN_REUSED.value()
    l1 = cache.get("nodeX")
    assert len(grants) == 1 and PEER_CONN_REUSED.value() == before
    l2 = cache.get("nodeX")
    assert l2 is l1 and len(grants) == 1
    assert PEER_CONN_REUSED.value() == before + 1
    # nothing idle yet
    assert cache.sweep_idle(idle_ttl_s=60.0) == []
    assert cache.hot_links(horizon_s=60.0) == ["lk-0"]
    # idle past the TTL: swept + closed
    l1.last_used = time.monotonic() - 120.0
    swept = cache.sweep_idle(idle_ttl_s=60.0)
    assert [l.link_id for l in swept] == ["lk-0"]
    assert cache.snapshot() == []
    # next use re-grants
    cache.get("nodeX")
    assert len(grants) == 2
    cache.close()


def test_steady_state_transfers_make_zero_head_rpcs(head, arena):
    """The acceptance property: after ONE GrantPeerLink, repeated
    cross-node transfers touch no head handler at all (handler-counter
    delta is empty across the window)."""
    from ray_tpu.cluster.rpc import HANDLER_STATS, RpcClient

    srv = tp.DataPlaneServer(arena, "nodeA", "sekrit", lambda: 5)
    try:
        payload = os.urandom(2 << 20)
        arena.put_bytes(OID_A, payload)
        _register_fake_node(
            head, "nodeA", endpoint=srv.endpoint, token="sekrit"
        )
        client = RpcClient(head.address)
        try:
            rep = client.call(
                "GrantPeerLink", {"src_node": "nodeB", "dst_node": "nodeA"}
            )
        finally:
            client.close()
        link = tp.PeerLink(
            rep["link_id"], "nodeA", rep["endpoint"], rep["token"], None
        )
        try:
            before = {
                k: v["count"] for k, v in HANDLER_STATS.snapshot().items()
            }
            for _ in range(5):
                assert bytes(tp.fetch_bytes(link, OID_A)) == payload
            after = {
                k: v["count"] for k, v in HANDLER_STATS.snapshot().items()
            }
            delta = {
                k: after.get(k, 0) - before.get(k, 0)
                for k in set(after) | set(before)
                if after.get(k, 0) != before.get(k, 0)
            }
            assert delta == {}, f"steady-state head RPCs: {delta}"
        finally:
            link.close()
    finally:
        srv.close()


def test_links_serve_across_head_restart_then_refence(
    arena, monkeypatch, tmp_path
):
    """Granted links keep serving while the head is DOWN (steady-state
    head-free), the restored head still tracks the row, and the epoch
    bump re-fences stale senders on the data-path handshake."""
    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.rpc import RpcClient

    monkeypatch.setenv("RAY_TPU_HEALTH_TIMEOUT_S", "300")
    path = str(tmp_path / "head_state.pkl")
    epoch_holder = [0]
    srv = tp.DataPlaneServer(
        arena, "nodeA", "sekrit", lambda: epoch_holder[0]
    )
    h1 = HeadServer(port=0, persist_path=path, use_device_scheduler=False)
    try:
        epoch_holder[0] = h1.cluster_epoch  # agent adopted at registration
        payload = os.urandom(1 << 20)
        arena.put_bytes(OID_A, payload)
        _register_fake_node(
            h1, "nodeA", endpoint=srv.endpoint, token="sekrit"
        )
        c = RpcClient(h1.address)
        try:
            rep = c.call(
                "GrantPeerLink", {"src_node": "nodeB", "dst_node": "nodeA"}
            )
        finally:
            c.close()
        link = tp.PeerLink(
            rep["link_id"],
            "nodeA",
            rep["endpoint"],
            rep["token"],
            rep["epoch"],
        )
        assert bytes(tp.fetch_bytes(link, OID_A)) == payload
        old_epoch = rep["epoch"]
        h1.shutdown()
        h1 = None
        # head is GONE: the granted link keeps serving (pooled conn AND
        # a fresh dial — the handshake needs no control plane)
        assert bytes(tp.fetch_bytes(link, OID_A)) == payload
        link.close()  # force the next fetch to re-dial + re-handshake
        assert bytes(
            tp.fetch_bytes(
                tp.PeerLink(
                    rep["link_id"], "nodeA", rep["endpoint"], rep["token"],
                    old_epoch,
                ),
                OID_A,
            )
        ) == payload
    finally:
        if h1 is not None:
            h1.shutdown()
    h2 = HeadServer(port=0, persist_path=path, use_device_scheduler=False)
    try:
        # restart restored the link-table row and bumped the epoch
        assert h2.cluster_epoch > old_epoch
        assert rep["link_id"] in h2._peer_links
        # the serving agent re-registers and adopts the new epoch: a
        # sender still stamping the OLD epoch is now fenced off the data
        # path at the handshake (re-grant is the resync)
        epoch_holder[0] = h2.cluster_epoch
        stale = tp.PeerLink(
            rep["link_id"], "nodeA", rep["endpoint"], rep["token"], old_epoch
        )
        with pytest.raises(tp.LinkRejectedError) as ei:
            tp.fetch_bytes(stale, OID_A)
        assert ei.value.code == tp.HS_STALE_EPOCH
        fresh = tp.PeerLink(
            rep["link_id"],
            "nodeA",
            rep["endpoint"],
            rep["token"],
            h2.cluster_epoch,
        )
        assert bytes(tp.fetch_bytes(fresh, OID_A)) == payload
    finally:
        h2.shutdown()
        srv.close()


# ---------------------------------------------------------------------------
# full-cluster integration (real agent subprocesses)
# ---------------------------------------------------------------------------


def _make_arr(n):
    import numpy as np

    return np.arange(n, dtype=np.float64)


def _touch_arr(x):
    return float(x[0] + x[-1])


def _two_node_cluster(env=None):
    from ray_tpu.cluster import Cluster

    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    cluster = Cluster(use_device_scheduler=False)
    try:
        a = cluster.add_node({"CPU": 2.0, "srcres": 1.0}, num_workers=1)
        b = cluster.add_node({"CPU": 2.0, "dstres": 1.0}, num_workers=1)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return cluster, a, b


def test_cluster_cross_node_pull_rides_socket_plane():
    """End to end through real agent subprocesses: a cross-node task-arg
    pull moves over the socket plane (server stripe counters grow on the
    source, a cached link appears on the destination, the head's link
    table shows the single grant), and repeated transfers of the same
    pair grant no further links."""
    import ray_tpu
    from ray_tpu.cluster.rpc import RpcClient
    from ray_tpu.core.runtime import set_runtime

    cluster, a, b = _two_node_cluster()
    rt = cluster.client()
    set_runtime(rt)
    try:
        mk = ray_tpu.remote(_make_arr).options(resources={"srcres": 0.1})
        tc = ray_tpu.remote(_touch_arr).options(resources={"dstres": 0.1})
        for _ in range(3):
            ref = mk.remote(1 << 20)  # 8 MB: over the inline threshold
            assert ray_tpu.get(tc.remote(ref), timeout=120) == 1048575.0
        agent_a = RpcClient(cluster.agent_address(a))
        agent_b = RpcClient(cluster.agent_address(b))
        try:
            net_a = agent_a.call("DebugState", {}, timeout=10)[
                "object_plane"
            ]["net"]
            net_b = agent_b.call("DebugState", {}, timeout=10)[
                "object_plane"
            ]["net"]
        finally:
            agent_a.close()
            agent_b.close()
        # >=1 not ==3: a transfer is ALLOWED to ride the chunked
        # fallback when its grant races — the property under test is
        # that the socket plane carries the steady state, not every
        # single pull
        assert net_a["server"]["stripes_served"] >= 1
        assert net_a["server"]["bytes_sent"] >= 8 << 20
        assert [l["node_id"] for l in net_b["links"]] == [a]
        assert net_b["links"][0]["transfers"] >= 1
        qs = rt.head.call(
            "QueryState", {"kind": "object_plane"}, timeout=10
        )
        assert qs["peer_link_count"] == 1
        assert qs["peer_links_granted"] == 1
    finally:
        set_runtime(None)
        rt.shutdown()
        cluster.shutdown()


@pytest.mark.slow
def test_cluster_kill_switch_falls_back_to_chunked_rpc():
    """RAY_TPU_NATIVE_NET=0 for the whole cluster: transfers produce the
    same values over the chunked-RPC path, no data server starts, and no
    peer link is ever granted."""
    import ray_tpu
    from ray_tpu.cluster.rpc import RpcClient
    from ray_tpu.core.runtime import set_runtime

    cluster, a, b = _two_node_cluster(env={"RAY_TPU_NATIVE_NET": "0"})
    rt = cluster.client()
    set_runtime(rt)
    try:
        mk = ray_tpu.remote(_make_arr).options(resources={"srcres": 0.1})
        tc = ray_tpu.remote(_touch_arr).options(resources={"dstres": 0.1})
        ref = mk.remote(1 << 20)
        assert ray_tpu.get(tc.remote(ref), timeout=120) == 1048575.0
        agent_a = RpcClient(cluster.agent_address(a))
        try:
            net_a = agent_a.call("DebugState", {}, timeout=10)[
                "object_plane"
            ]["net"]
        finally:
            agent_a.close()
        assert net_a["server"] is None  # kill switch: no data plane
        qs = rt.head.call(
            "QueryState", {"kind": "object_plane"}, timeout=10
        )
        assert qs["peer_links_granted"] == 0
    finally:
        set_runtime(None)
        rt.shutdown()
        cluster.shutdown()


@pytest.mark.slow
def test_cluster_node_death_mid_stripe_reconstructs():
    """Source-node death during a striped cross-node pull: the socket
    plane fails over (chunked fallback -> locate loop), the head prunes
    the dead location, and lineage reconstruction re-executes the
    producer on the replacement node — the consumer still gets the exact
    value (zero acked loss)."""
    import ray_tpu
    from ray_tpu.core.runtime import set_runtime

    cluster, a, b = _two_node_cluster(
        env={
            # small stripes lengthen the transfer window the kill lands in
            "RAY_TPU_NET_STRIPE_BYTES": str(1 << 20),
            "RAY_TPU_HEALTH_TIMEOUT_S": "4.0",
        }
    )
    rt = cluster.client()
    set_runtime(rt)
    try:
        mk = ray_tpu.remote(_make_arr).options(
            resources={"srcres": 0.1}, max_retries=2
        )
        tc = ray_tpu.remote(_touch_arr).options(resources={"dstres": 0.1})
        ref = mk.remote(12 << 20)  # 96 MB
        ray_tpu.wait([ref], timeout=300)
        got = {}

        def consume():
            try:
                got["v"] = ray_tpu.get(tc.remote(ref), timeout=300)
            except BaseException as exc:  # noqa: BLE001
                got["err"] = exc

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.5)  # let the cross-node pull start
        cluster.kill_node(a)
        # replacement capacity so the producer can re-execute
        cluster.add_node({"CPU": 2.0, "srcres": 1.0}, num_workers=1)
        t.join(timeout=300)
        assert not t.is_alive()
        assert "err" not in got, f"consumer failed: {got.get('err')!r}"
        assert got["v"] == float(0 + ((12 << 20) - 1))
    finally:
        set_runtime(None)
        rt.shutdown()
        cluster.shutdown()


# ---------------------------------------------------------------------------
# fetch_chunked relocate fix
# ---------------------------------------------------------------------------


class _DeadPeer:
    """Fake RPC client whose data calls always fail at transport level."""

    def __init__(self):
        self.calls = 0

    def call(self, method, payload=None, **kw):
        if method == "FetchObjectMeta":
            return {"size": 3 * (4 << 20)}  # 3 chunks at the default size
        self.calls += 1
        raise ConnectionError("peer is dead")


def test_fetch_chunked_aborts_fast_when_source_is_gone():
    """The relocate hook re-resolves the source between chunk retries: a
    gone-everywhere verdict aborts the whole pull immediately instead of
    burning every chunk's full retry budget against a dead peer."""
    from ray_tpu.cluster.object_plane import ChunkFetchError, fetch_chunked

    peer = _DeadPeer()
    with pytest.raises(ChunkFetchError) as ei:
        fetch_chunked(peer, OID_A, relocate=lambda: None)
    assert "re-plan" in str(ei.value)
    # without relocation every chunk would have retried 3x (9 calls);
    # the abort path stops after the first failures' re-resolve
    assert peer.calls <= 4


def test_fetch_chunked_switches_to_relocated_replica():
    """A mid-pull relocation continues the SAME pull from the replica
    the directory moved the object to."""
    from ray_tpu.cluster.object_plane import fetch_chunked

    chunk = 4 << 20
    blob = os.urandom(2 * chunk + 100)

    class _Healthy:
        def call(self, method, payload=None, **kw):
            assert method == "FetchObjectChunk"
            off = payload["offset"]
            return blob[off : off + payload["length"]]

    class _DiesOnce:
        def __init__(self):
            self.failed = False

        def call(self, method, payload=None, **kw):
            if method == "FetchObjectMeta":
                return {"size": len(blob)}
            if not self.failed:
                self.failed = True
                raise ConnectionError("sever")
            raise ConnectionError("still dead")

    healthy = _Healthy()
    out = fetch_chunked(_DiesOnce(), OID_A, relocate=lambda: healthy)
    assert bytes(out) == blob
