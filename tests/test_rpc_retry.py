"""RetryableGrpcClient analog: backoff, caller deadlines, circuit breaker.

Reference contract: src/ray/rpc/retryable_grpc_client.h — exponential
backoff between retries, a server-unavailable timeout after which the
client gives up and fires a callback, and caller deadlines that bound
the whole retry sequence.
"""
import time

import pytest

from ray_tpu.cluster.rpc import (
    FAULTS,
    PeerUnavailableError,
    RpcClient,
    RpcDeadlineError,
    RpcError,
    RpcServer,
    get_breaker,
    reset_breakers,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.clear()
    reset_breakers()
    yield
    FAULTS.clear()
    reset_breakers()


def _dead_address() -> str:
    """An address with nothing listening (bind, grab the port, close)."""
    srv = RpcServer({"Echo": lambda r: r})
    addr = srv.address
    srv.stop()
    return addr


def test_roundtrip_and_server_exception():
    srv = RpcServer({"Echo": lambda r: r, "Boom": lambda r: 1 / 0})
    c = RpcClient(srv.address)
    try:
        assert c.call("Echo", {"x": 1}) == {"x": 1}
        with pytest.raises(ZeroDivisionError):
            c.call("Boom")
    finally:
        c.close()
        srv.stop()


def test_retry_sequence_respects_caller_deadline():
    """No retry sequence exceeds the caller's overall timeout: a huge
    retry budget against a dead peer must stop at deadline_s."""
    c = RpcClient(_dead_address())
    t0 = time.monotonic()
    with pytest.raises(RpcDeadlineError):
        c.call(
            "Echo",
            1,
            timeout=30.0,
            retries=10_000,
            retry_interval=0.02,
            deadline_s=0.6,
        )
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0, f"retry loop overran the 0.6s deadline: {elapsed}"
    c.close()


def test_deadline_error_is_an_rpc_error():
    """Existing except-RpcError recovery paths must catch deadline
    exhaustion too."""
    assert issubclass(RpcDeadlineError, RpcError)
    assert issubclass(PeerUnavailableError, RpcError)


def test_backoff_sleeps_are_capped(monkeypatch):
    """Backoff grows but never exceeds the configured cap."""
    monkeypatch.setenv("RAY_TPU_RPC_BACKOFF_CAP_S", "0.05")
    sleeps = []
    real_sleep = time.sleep
    monkeypatch.setattr(
        time, "sleep", lambda s: (sleeps.append(s), real_sleep(min(s, 0.01)))
    )
    c = RpcClient(_dead_address())
    with pytest.raises(RpcError):
        c.call("Echo", 1, timeout=0.2, retries=6, retry_interval=0.01)
    c.close()
    assert len(sleeps) == 6
    assert all(s <= 0.05 + 1e-9 for s in sleeps), sleeps
    assert all(s >= 0.01 - 1e-9 for s in sleeps), sleeps


def test_breaker_opens_within_window_under_blackholed_peer(monkeypatch):
    """A blackholed peer's circuit opens once failures span the
    configured server-unavailable window, then calls fail fast."""
    monkeypatch.setenv("RAY_TPU_RPC_BREAKER_WINDOW_S", "0.3")
    monkeypatch.setenv("RAY_TPU_RPC_BREAKER_COOLDOWN_S", "5.0")
    srv = RpcServer({"Echo": lambda r: r})
    fired = []
    c = RpcClient(srv.address, on_unreachable=lambda: fired.append(1))
    FAULTS.blackhole(srv.address)
    br = get_breaker(srv.address)
    t0 = time.monotonic()
    while br.state != br.OPEN:
        with pytest.raises(RpcError):
            c.call("Echo", 1, retries=0)
        time.sleep(0.03)
        assert time.monotonic() - t0 < 3.0, "breaker never opened"
    opened_after = time.monotonic() - t0
    assert 0.25 <= opened_after < 2.0, opened_after
    assert fired, "node-unreachable callback did not fire"
    # open circuit: fail fast, no wire, no per-attempt timeout burned
    t1 = time.monotonic()
    with pytest.raises(PeerUnavailableError):
        c.call("Echo", 1, timeout=30.0)
    assert time.monotonic() - t1 < 0.05
    c.close()
    srv.stop()


def test_breaker_half_open_probe_recovers(monkeypatch):
    monkeypatch.setenv("RAY_TPU_RPC_BREAKER_WINDOW_S", "0.2")
    monkeypatch.setenv("RAY_TPU_RPC_BREAKER_COOLDOWN_S", "0.2")
    srv = RpcServer({"Echo": lambda r: r})
    c = RpcClient(srv.address)
    FAULTS.blackhole(srv.address)
    br = get_breaker(srv.address)
    deadline = time.monotonic() + 3.0
    while br.state != br.OPEN and time.monotonic() < deadline:
        with pytest.raises(RpcError):
            c.call("Echo", 1, retries=0)
        time.sleep(0.03)
    assert br.state == br.OPEN
    # heal the partition: a patient retry loop rides the half-open probe
    # back to a closed circuit
    FAULTS.heal(srv.address)
    assert c.call("Echo", 7, retries=10, retry_interval=0.1) == 7
    assert br.state == br.CLOSED
    c.close()
    srv.stop()


def test_straggler_delay_injection():
    srv = RpcServer({"Echo": lambda r: r})
    c = RpcClient(srv.address)
    FAULTS.set_delay(srv.address, 0.15)
    t0 = time.monotonic()
    assert c.call("Echo", 1) == 1
    assert time.monotonic() - t0 >= 0.14
    FAULTS.heal(srv.address)
    t1 = time.monotonic()
    assert c.call("Echo", 2) == 2
    assert time.monotonic() - t1 < 0.1
    c.close()
    srv.stop()


def test_breaker_shared_across_clients_to_same_peer(monkeypatch):
    monkeypatch.setenv("RAY_TPU_RPC_BREAKER_WINDOW_S", "0.2")
    monkeypatch.setenv("RAY_TPU_RPC_BREAKER_COOLDOWN_S", "30.0")
    srv = RpcServer({"Echo": lambda r: r})
    c1 = RpcClient(srv.address)
    c2 = RpcClient(srv.address)
    FAULTS.blackhole(srv.address)
    br = get_breaker(srv.address)
    deadline = time.monotonic() + 3.0
    while br.state != br.OPEN and time.monotonic() < deadline:
        with pytest.raises(RpcError):
            c1.call("Echo", 1, retries=0)
        time.sleep(0.03)
    assert br.state == br.OPEN
    # the OTHER client to the same peer fails fast too: breaker state is
    # per peer, not per channel
    with pytest.raises(PeerUnavailableError):
        c2.call("Echo", 1, timeout=30.0)
    c1.close()
    c2.close()
    srv.stop()


def test_a_fresh_server_on_a_reused_port_is_reached_at_once():
    """A client that outlived its server is reconnecting with backoff. A
    new server takes the port (ephemeral ports come round again in a long
    process); a new client's first call reaches it, whatever the old
    client's channel is waiting for."""
    old_srv = RpcServer({"Echo": lambda r: "old"})
    port = old_srv.port
    stale = RpcClient(old_srv.address)
    fresh = srv = None
    try:
        assert stale.call("Echo", timeout=5.0) == "old"
        old_srv.stop(0)
        with pytest.raises(RpcError):  # a failed connect: backoff begins
            stale.call("Echo", timeout=2.0)
        srv = RpcServer({"Echo": lambda r: "new"}, port=port)
        fresh = RpcClient(srv.address)
        assert fresh.call("Echo", timeout=5.0) == "new"
    finally:
        for c in (stale, fresh):
            if c is not None:
                c.close()
        if srv is not None:
            srv.stop()
