"""Online-RL continuous-learning loop (ISSUE 20).

Fast tier: the trajectory plane's conservation law + staleness window,
the two-phase (seal -> commit) weights-epoch fence across head crashes
at every phase boundary (persistence replay + standby promotion), the
publisher's retry-to-exactly-one-epoch behaviour, and the engine-level
hot-swap drain (token-exact on the old epoch; bounded by
``serve_swap_drain_deadline_s`` with typed ``Overloaded`` shedding).

Slow tier: the triple-plane chaos soak — one run in which a rollout
replica is SIGKILLed mid-trajectory, a trainer-rank node is SIGKILLed
mid-step, and the head is SIGKILLed INSIDE a seal->commit window —
asserting token-exact stream resume, gang reshape with loss-curve
continuity, publish atomicity across the promotion, weights-epoch
convergence, and zero unaccounted trajectories.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.core.runtime import set_runtime
from ray_tpu.models import transformer as tfm


def _wait_for(cond, timeout=60.0, every=0.1, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(every)
    if not cond():
        raise TimeoutError(f"timed out waiting for {msg}")


def _small_cfg(**over):
    base = dict(
        vocab_size=64,
        d_model=48,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=96,
        dtype=jnp.float32,
    )
    base.update(over)
    return tfm.ModelConfig(**base)


def _traj(tid, epoch, toks=(1, 2, 3, 4)):
    from ray_tpu.rl import Trajectory

    return Trajectory(
        traj_id=tid,
        prompt=list(toks[:2]),
        tokens=list(toks),
        weights_epoch=epoch,
        rollout_id="r0",
    )


def _kill_head(head):
    """SIGKILL-equivalent for an in-process HeadServer (mirrors
    Cluster.kill_head): listener drops mid-flight, no final snapshot is
    flushed — the persistence dir holds only what the WAL already has."""
    head._shutdown = True
    with head._cond:
        head._cond.notify_all()
    head._repl.stop()
    head._server.stop(grace=0)
    if head._pipeline is not None:
        try:
            head._pipeline.stop()
        except Exception:  # noqa: BLE001
            pass
    head._dispatch_pool.shutdown(wait=False, cancel_futures=True)
    try:
        head.jobs.shutdown()
    except Exception:  # noqa: BLE001
        pass
    with head._lock:
        clients = list(head._clients.values())
    for client in clients:
        try:
            client.close()
        except Exception:  # noqa: BLE001
            pass


# ---------------------------------------------------------------------------
# trajectory plane: dedup, staleness window, idempotent step batches
# ---------------------------------------------------------------------------
def test_feed_staleness_window_boundary_and_dedup():
    """Epoch == committed - K is ON the boundary and kept; older is
    dropped AND counted; duplicate traj_ids never enter ``emitted``;
    the conservation law balances throughout."""
    from ray_tpu.rl import TrajectoryFeed, encode_block

    feed = TrajectoryFeed(staleness_window=2)
    feed.emit(
        encode_block(
            [_traj("a", 2), _traj("b", 3), _traj("c", 5), _traj("d", 5)]
        )
    )
    # duplicate re-emit (a resumed rollout re-delivering) is benign
    dup = feed.emit(encode_block([_traj("b", 3)]))
    assert dup == {"accepted": 0, "duplicates": 1}
    acct = feed.accounting()
    assert acct["emitted"] == 4 and acct["duplicates"] == 1
    assert acct["unaccounted"] == 0

    # floor = 5 - 2 = 3: epoch 2 dropped, epoch 3 (boundary) kept
    block = feed.take_for_step(0, 8, current_epoch=5, staleness_window=2)
    got = sorted(block["traj_ids"])
    assert got == ["b", "c", "d"]
    assert 3 in [int(e) for e in block["epochs"]]
    acct = feed.accounting()
    assert acct["dropped_stale"] == 1
    assert acct["trained"] == 3
    assert acct["unaccounted"] == 0


def test_feed_step_batches_idempotent_including_empty():
    """``take_for_step`` replays return the identical cached block — and
    a step that originally saw an empty buffer stays empty on replay
    (gang-reshape replays must not train data the recorded run never
    saw). Nothing double-counts."""
    from ray_tpu.rl import TrajectoryFeed, encode_block

    feed = TrajectoryFeed(staleness_window=2)
    # step 0 forms before anything was emitted: cached as empty
    assert feed.take_for_step(0, 4) is None
    feed.emit(encode_block([_traj(f"t{i}", 1) for i in range(6)]))
    assert feed.take_for_step(0, 4) is None  # replay: still empty
    b1 = feed.take_for_step(1, 4)
    b1_replay = feed.take_for_step(1, 4)
    assert b1["traj_ids"] == b1_replay["traj_ids"]
    assert np.array_equal(b1["tokens"], b1_replay["tokens"])
    b2 = feed.take_for_step(2, 4)
    assert len(b2["traj_ids"]) == 2
    acct = feed.accounting()
    assert acct["trained"] == 6 and acct["unaccounted"] == 0


# ---------------------------------------------------------------------------
# two-phase publish fence: crash points via persistence replay
# ---------------------------------------------------------------------------
def test_seal_crash_leaves_old_epoch_fully_visible(tmp_path):
    """Head killed AFTER seal but BEFORE commit: the restarted head
    shows the OLD committed epoch with a dangling seal — never a torn
    in-between — and a retried publish lands exactly one epoch."""
    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.rpc import RpcClient
    from ray_tpu.rl import WeightsPublisher

    head = HeadServer(
        port=0,
        persist_path=str(tmp_path / "h"),
        use_device_scheduler=False,
    )
    c = RpcClient(head.address)
    sealed = c.call(
        "WeightsPublishSeal", {"deployment": "pol", "meta": {}}, timeout=10.0
    )
    assert sealed == {"epoch": 1, "committed": 0}
    c.close()
    _kill_head(head)  # crash inside the window: commit never happened

    head2 = HeadServer(
        port=0,
        persist_path=str(tmp_path / "h"),
        use_device_scheduler=False,
    )
    try:
        c2 = RpcClient(head2.address)
        st = c2.call("WeightsEpochGet", {"deployment": "pol"}, timeout=10.0)
        assert st["committed"] == 0  # old epoch fully visible
        assert st["sealed"] == {"epoch": 1, "meta": {}}  # dangling seal
        c2.close()
        pub = WeightsPublisher("pol", head_address=head2.address)
        try:
            assert pub.publish({"w": 1}) == 1  # retry re-seals and lands
            assert pub.current_epoch()["committed"] == 1
            assert pub.current_epoch()["sealed"] is None
        finally:
            pub.close()
    finally:
        head2.shutdown()


def test_commit_crash_keeps_new_epoch(tmp_path):
    """Head killed right AFTER commit: the WAL commit record replays and
    the restarted head shows the NEW epoch, seal consumed. A re-commit
    of the same epoch (lost reply) is idempotent, not stale."""
    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.rpc import RpcClient

    head = HeadServer(
        port=0,
        persist_path=str(tmp_path / "h"),
        use_device_scheduler=False,
    )
    c = RpcClient(head.address)
    c.call("WeightsPublishSeal", {"deployment": "pol", "meta": {}},
           timeout=10.0)
    r = c.call(
        "WeightsPublishCommit", {"deployment": "pol", "epoch": 1},
        timeout=10.0,
    )
    assert r == {"committed": 1, "stale": False}
    c.close()
    _kill_head(head)

    head2 = HeadServer(
        port=0,
        persist_path=str(tmp_path / "h"),
        use_device_scheduler=False,
    )
    try:
        c2 = RpcClient(head2.address)
        st = c2.call("WeightsEpochGet", {"deployment": "pol"}, timeout=10.0)
        assert st["committed"] == 1 and st["sealed"] is None
        # idempotent re-commit after a lost reply
        again = c2.call(
            "WeightsPublishCommit", {"deployment": "pol", "epoch": 1},
            timeout=10.0,
        )
        assert again == {"committed": 1, "stale": False}
        # a commit for a never-sealed epoch is fenced stale
        bogus = c2.call(
            "WeightsPublishCommit", {"deployment": "pol", "epoch": 2},
            timeout=10.0,
        )
        assert bogus == {"committed": 1, "stale": True}
        c2.close()
    finally:
        head2.shutdown()


def test_publisher_retries_whole_cycle_on_stale_commit():
    """A promoted head that never saw the seal record answers the commit
    ``stale``; the publisher restarts the WHOLE cycle (re-seal, re-stash,
    commit) and exactly one epoch lands."""
    from ray_tpu.rl import WeightsPublisher

    pub = WeightsPublisher("pol")  # LocalEpochLedger
    calls = []

    def lose_seal_once(epoch):
        calls.append(epoch)
        if len(calls) == 1:
            # simulate the standby that the seal never replicated to
            with pub._client._lock:
                pub._client._row("pol")["sealed"] = None

    pub.between_phases = lose_seal_once
    assert pub.publish({"w": 1}) == 1
    assert calls == [1, 1]  # one stale round-trip, then the retry landed
    st = pub.current_epoch()
    assert st["committed"] == 1 and st["sealed"] is None
    pub.close()


def test_publish_replicates_to_standby_and_survives_promotion(tmp_path):
    """Committed epochs (and dangling seals) replicate to the warm
    standby; after the leader dies and the standby promotes onto the
    leader's port, the SAME publisher keeps publishing — the fence only
    ever moves forward."""
    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.standby import StandbyHead
    from ray_tpu.rl import WeightsPublisher

    head = HeadServer(
        port=0,
        persist_path=str(tmp_path / "h"),
        use_device_scheduler=False,
    )
    sb = StandbyHead(head.address, auto_promote=False)
    head2 = None
    pub = WeightsPublisher("pol", head_address=head.address)
    try:
        assert pub.publish({"w": 1}) == 1
        assert pub.publish({"w": 2}) == 2
        _wait_for(
            lambda: sb.tables_snapshot()
            .get("weights_epochs", {})
            .get("pol", {})
            .get("committed")
            == 2,
            timeout=20.0,
            msg="weights_epochs replicated to standby",
        )
        _kill_head(head)
        head2 = sb.promote()  # binds the dead leader's port
        # the publisher's RpcClient reconnects to the same address
        assert pub.publish({"w": 3}) == 3
        st = pub.current_epoch()
        assert st["committed"] == 3 and st["sealed"] is None
    finally:
        pub.close()
        sb.shutdown()
        if head2 is not None:
            head2.shutdown()


def test_head_killed_inside_publish_window_is_atomic(tmp_path):
    """The mid-publish crash point itself: the leader dies BETWEEN seal
    and commit, the standby promotes on the same port, and the
    publisher's in-flight publish retries until exactly one epoch is
    committed — old or new, never torn."""
    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.standby import StandbyHead
    from ray_tpu.rl import WeightsPublisher

    head = HeadServer(
        port=0,
        persist_path=str(tmp_path / "h"),
        use_device_scheduler=False,
    )
    sb = StandbyHead(head.address, auto_promote=False)

    def _registered():
        from ray_tpu.cluster.rpc import RpcClient

        c = RpcClient(head.address)
        try:
            st = c.call("QueryState", {"kind": "replication"}, timeout=5.0)
            return bool(st.get("standbys"))
        finally:
            c.close()

    _wait_for(_registered, timeout=15.0, msg="standby registered")
    pub = WeightsPublisher("pol", head_address=head.address)
    killed = []

    def kill_in_window(epoch):
        if killed:
            return
        killed.append(epoch)
        _kill_head(head)
        sb.promote()  # same port: the retry reconnects transparently

    pub.between_phases = kill_in_window
    head2 = None
    try:
        epoch = pub.publish({"w": 1})
        head2 = sb.promoted
        assert killed == [1]
        assert epoch == 1
        st = pub.current_epoch()
        # atomicity: committed is exactly the returned epoch, seal gone
        assert st["committed"] == epoch and st["sealed"] is None
    finally:
        pub.close()
        sb.shutdown()
        if head2 is not None:
            head2.shutdown()


# ---------------------------------------------------------------------------
# engine hot-swap: token-exact drain + bounded drain with typed shed
# ---------------------------------------------------------------------------
def test_swap_params_mid_stream_drains_token_exact():
    """Requests in flight when ``swap_params`` lands finish their whole
    generation on the OLD weights (token-exact vs a never-swapped twin);
    requests after the swap match the NEW-weights twin."""
    from ray_tpu.llm.continuous import ContinuousBatchingEngine
    from ray_tpu.llm.engine import GenerationConfig

    mcfg = _small_cfg()
    old_params = tfm.init_params(mcfg, jax.random.PRNGKey(7))
    new_params = tfm.init_params(mcfg, jax.random.PRNGKey(8))
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0)
    prompt = [1, 2, 3, 4]

    ref_old = ContinuousBatchingEngine(
        mcfg, old_params, max_batch=2, page_size=8, n_pages=32
    ).generate_ids([prompt], gen)[0]
    ref_new = ContinuousBatchingEngine(
        mcfg, new_params, max_batch=2, page_size=8, n_pages=32
    ).generate_ids([prompt], gen)[0]

    eng = ContinuousBatchingEngine(
        mcfg, old_params, max_batch=2, page_size=8, n_pages=32,
        model_id="epoch-0",
    )
    rid = eng.submit(list(prompt), gen)
    for _ in range(3):  # mid-generation
        eng.step()
    assert rid not in eng.results
    epoch = eng.swap_params(new_params, model_id="epoch-1")
    assert epoch == 1 and eng.model_id == "epoch-1"
    # the drained stream never mixed epochs: byte-identical to the
    # old-weights reference
    assert eng.results.pop(rid) == ref_old
    assert eng.generate_ids([prompt], gen)[0] == ref_new


def test_swap_drain_deadline_force_evicts_and_sheds(monkeypatch):
    """A wedged drain is bounded: past ``serve_swap_drain_deadline_s``
    still-active slots are force-evicted with their partial output
    recorded, pages freed, and the swap lands; admission during an
    expired drain sheds typed ``Overloaded(reason="weights_swap")``."""
    from ray_tpu.llm.continuous import ContinuousBatchingEngine
    from ray_tpu.llm.engine import GenerationConfig
    from ray_tpu.serve.admission import Overloaded

    # a deadline so tight the drain loop trips it after at most one step
    # (warmed CPU decode finishes 64 tokens in a few ms, so a realistic
    # deadline would drain clean and never exercise the eviction path)
    monkeypatch.setenv("RAY_TPU_SERVE_SWAP_DRAIN_DEADLINE_S", "0.0001")
    mcfg = _small_cfg()
    params = tfm.init_params(mcfg, jax.random.PRNGKey(7))
    new_params = tfm.init_params(mcfg, jax.random.PRNGKey(8))
    eng = ContinuousBatchingEngine(
        mcfg, params, max_batch=2, page_size=8, n_pages=32
    )
    # warm the decode compile so the pre-swap steps below emit tokens
    eng.generate_ids([[1, 2, 3]], GenerationConfig(max_new_tokens=1))
    free_before = eng.pool.free_pages
    rid = eng.submit([1, 2, 3, 4], GenerationConfig(max_new_tokens=64))
    eng.step()
    eng.step()  # a couple of tokens in flight before the swap begins
    epoch = eng.swap_params(new_params, model_id="epoch-1")
    assert epoch >= 1
    assert eng.swap_force_evicted == 1
    out = eng.results.pop(rid)
    assert 0 < len(out) < 64  # partial output recorded, reader unblocks
    assert not any(s.active for s in eng.slots)
    assert eng.pool.free_pages == free_before  # pages freed
    assert eng.stats()["swap_force_evicted"] == 1

    # typed shed while a drain has outlived its deadline
    eng._swapping = True
    eng._swap_started = time.monotonic() - 10.0
    try:
        with pytest.raises(Overloaded) as ei:
            eng.submit([1, 2, 3], GenerationConfig(max_new_tokens=4))
        assert ei.value.reason == "weights_swap"
        assert ei.value.retry_after_s > 0
    finally:
        eng._swapping = False
        eng._swap_started = None


# ---------------------------------------------------------------------------
# the in-process loop: deterministic fenced cycle
# ---------------------------------------------------------------------------
def test_online_rl_loop_fenced_and_deterministic():
    """Two loops built from identical inputs produce identical loss
    curves (the continuity oracle); every published epoch reaches every
    rollout worker; the conservation law balances at the end."""
    from ray_tpu.rl import OnlineRLLoop, RLLoopConfig

    mcfg = _small_cfg(d_model=32, n_layers=1, d_ff=64, max_seq_len=64)
    params = tfm.init_params(mcfg, jax.random.PRNGKey(5))
    lc = RLLoopConfig(
        n_rollout_workers=2,
        prompts_per_step=2,
        prompt_len=6,
        max_new_tokens=6,
        batch_size=4,
        total_steps=6,
        seed=11,
        publish_interval=2,
        staleness_window=2,
    )

    def run_once():
        loop = OnlineRLLoop(mcfg, params, lc)
        try:
            res = loop.run()
            epochs = [w.weights_epoch for w in loop.workers]
            models = [w.engine.model_id for w in loop.workers]
            return res, epochs, models
        finally:
            loop.close()

    res_a, epochs_a, models_a = run_once()
    res_b, _, _ = run_once()
    assert res_a["weights_epoch"] == 3  # 6 steps / publish_interval 2
    assert epochs_a == [3, 3]  # every worker hot-swapped to the fence
    assert models_a == ["epoch-3", "epoch-3"]
    assert res_a["losses"] == res_b["losses"]  # bit-exact continuity
    assert len(res_a["losses"]) == 6
    assert res_a["accounting"]["unaccounted"] == 0
    assert len(res_a["publish_to_first_token_ms"]) == 3
    assert res_a["samples_trained"] == 24


# ---------------------------------------------------------------------------
# slow tier: the triple-plane chaos soak
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_rl_triple_chaos_soak(tmp_path):
    """One run, three planes of chaos: a rollout replica SIGKILLed
    mid-trajectory (token-exact resume + trajectory dedup), a
    trainer-rank node SIGKILLed mid-step (gang reshape + loss-curve
    continuity against a shadow trainer replaying the identical step
    batches), and the head SIGKILLed INSIDE a seal->commit window
    (standby promotes; publish atomicity). After every fault: weights
    epochs converge and zero trajectories go unaccounted."""
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.chaos import (
        ChaosOrchestrator,
        ChaosWorkload,
        RL_MIX,
        RLRolloutWorkload,
        make_plan,
    )
    from ray_tpu.cluster import Cluster
    from ray_tpu.llm.continuous import ContinuousBatchingEngine
    from ray_tpu.llm.engine import GenerationConfig
    from ray_tpu.llm.serving import build_llm_deployment
    from ray_tpu.rl import (
        TrajectoryFeed,
        WeightsPublisher,
        elastic_rl_init,
        elastic_rl_step,
        model_config_to_dict,
    )
    from ray_tpu.train import ElasticConfig, ElasticTrainer

    # the serve plane byte-tokenizes prompts (ids up to bos=256), and the
    # trainer computes CE loss over those same token ids — the model vocab
    # must cover the tokenizer or loss_fn NaNs on out-of-vocab labels
    mcfg = _small_cfg(vocab_size=258)
    prompt = "rl rollout"
    max_new = 8
    gen = GenerationConfig(max_new_tokens=max_new, temperature=0.0, seed=0)
    # replicas init from PRNGKey(0) when params=None; the trainer seeds
    # from config["seed"]=0 — one base model everywhere
    base_params = tfm.init_params(mcfg, jax.random.PRNGKey(0))
    ref_engine = ContinuousBatchingEngine(
        mcfg, None, max_batch=2, page_size=8, n_pages=64
    )

    def expected_tokens():
        return [
            ref_engine.tokenizer.decode([int(t)])
            for t in ref_engine.stream_ids(
                ref_engine.tokenizer.encode(prompt), gen
            )
        ]

    expected_base = expected_tokens()
    assert len(expected_base) == max_new

    # head persistence is what feeds WAL shipping to the armed standby
    cluster = Cluster(
        use_device_scheduler=False,
        persist_path=str(tmp_path / "head_state.pkl"),
    )
    cluster.add_node({"CPU": 8.0}, num_workers=3)
    cluster.add_node({"CPU": 8.0}, num_workers=3)
    # the feed actor gets its own tiny node so trainer_rank_kill (which
    # SIGKILLs a node hosting trainer ranks) can never take the
    # accounting ledger down with it
    cluster.add_node({"CPU": 0.5, "FEED": 1.0}, num_workers=1)
    rt = cluster.client()
    set_runtime(rt)
    cluster.start_standby(auto_promote=False)
    workload = None
    pump = None
    stop_evt = threading.Event()
    try:
        FeedActor = ray_tpu.remote(TrajectoryFeed)
        feed = FeedActor.options(
            name="rl-feed", num_cpus=0.25, resources={"FEED": 1.0}
        ).remote(2)
        ray_tpu.get(feed.latest_epoch.remote(), timeout=60.0)

        app = build_llm_deployment(
            mcfg,
            name="rl-policy",
            num_replicas=2,
            max_batch=2,
            page_size=8,
            n_pages=64,
        )
        serve.run(app)
        router = serve.get_router("rl-policy")
        assert router.resumable

        publisher = WeightsPublisher(
            "rl-policy", head_address=cluster.address
        )
        payload = {"prompt": prompt, "max_new_tokens": max_new}
        workload = RLRolloutWorkload(
            router,
            payload,
            {"base": expected_base},
            publisher=publisher,
            feed=feed,
            concurrency=2,
            # hashed trajectory ids must live inside the trainer model's
            # vocab — OOV labels NaN the CE loss on both curve and shadow
            token_space=mcfg.vocab_size,
        )
        workload.start()
        _wait_for(
            lambda: workload.completed >= 2,
            timeout=240.0,
            msg="warm rollout streams",
        )
        assert not workload.verify_failures

        # throttled through the fault schedule (the trainer must outlive
        # every fault), sprinted to the finish once chaos is done
        ray_tpu.get(feed.set_pace.remote(0.2), timeout=30.0)
        trainer = ElasticTrainer(
            elastic_rl_init,
            elastic_rl_step,
            total_steps=2500,
            train_loop_config={
                "model": model_config_to_dict(mcfg),
                "seed": 0,
                "batch_size": 4,
                "lr": 0.01,
                "feed_actor": "rl-feed",
            },
            elastic_config=ElasticConfig(
                min_workers=1,
                max_workers=2,
                virtual_shards=4,
                seal_interval_steps=2,
                grow=True,
                placement_strategy="STRICT_SPREAD",
                resources_per_worker={"CPU": 1.0},
            ),
        )
        workload.trainer = trainer
        fit_box = {}

        def _fit():
            try:
                fit_box["res"] = trainer.fit()
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                fit_box["exc"] = exc

        fit_th = threading.Thread(target=_fit, daemon=True)
        fit_th.start()
        _wait_for(
            lambda: trainer.progress()["step"] >= 2 or "exc" in fit_box,
            timeout=240.0,
            msg="trainer first steps",
        )
        if "exc" in fit_box:
            raise fit_box["exc"]

        # shadow trainer + publish pump: replays the feed's cached step
        # batches in the driver (byte-identical to what the gang
        # trained), publishes the shadow params under the two-phase
        # fence, hot-swaps every replica, and registers the new epoch's
        # reference sequence for verification
        shadow = {"params": base_params, "step": 0}
        shadow_losses = {}
        pump_errors = []

        def _pump():
            while not stop_evt.is_set():
                try:
                    target = trainer.progress()["step"]
                    while shadow["step"] < target and not stop_evt.is_set():
                        s = shadow["step"]
                        block = ray_tpu.get(
                            feed.take_for_step.remote(s, 4), timeout=60.0
                        )
                        if block is not None:
                            tokens = jnp.asarray(np.asarray(block["tokens"]))
                            loss, grads = jax.value_and_grad(
                                lambda p: tfm.loss_fn(p, tokens, mcfg)
                            )(shadow["params"])
                            shadow["params"] = jax.tree.map(
                                lambda p, g: p - 0.01 * g,
                                shadow["params"],
                                grads,
                            )
                            shadow_losses[s] = float(loss)
                        shadow["step"] = s + 1
                    epoch = publisher.publish(shadow["params"])
                    ray_tpu.get(feed.note_epoch.remote(epoch), timeout=30.0)
                    model_id = f"epoch-{epoch}"
                    ref_engine.swap_params(
                        shadow["params"], model_id=model_id
                    )
                    expected = expected_tokens()
                    workload.broadcast_weights(
                        shadow["params"], model_id, epoch
                    )
                    workload.register_model(model_id, expected)
                except Exception as exc:  # noqa: BLE001 - head mid-failover
                    pump_errors.append(repr(exc))
                stop_evt.wait(1.0)

        pump = threading.Thread(target=_pump, daemon=True)
        pump.start()
        try:
            _wait_for(
                lambda: workload.published_epoch() >= 1,
                timeout=120.0,
                msg="first weights publish",
            )
        except TimeoutError as exc:
            raise AssertionError(
                f"first publish never landed; shadow_step={shadow['step']} "
                f"pump_errors={pump_errors[-5:]}"
            ) from exc

        plan = make_plan(
            seed=14,
            num_faults=4,
            mix=RL_MIX,
            allow=(
                "rollout_kill",
                "trainer_rank_kill",
                "head_kill_mid_publish",
            ),
            min_delay_s=0.5,
            max_delay_s=1.5,
        )
        # all three planes in ONE run (seed pinned for that property)
        assert set(plan.counts()) == {
            "rollout_kill",
            "trainer_rank_kill",
            "head_kill_mid_publish",
        }
        chaos_wl = ChaosWorkload(rt, payload_bytes=150_000, num_actors=1)
        orch = ChaosOrchestrator(
            cluster,
            chaos_wl,
            plan,
            node_resources={"CPU": 8.0},
            workers_per_node=3,
            convergence_budget_s=180.0,
            serve_adapter=workload,
            rl_adapter=workload,
        )
        result = orch.run()
        stop_evt.set()
        workload.stop()
        # cooperative finish now that chaos is over: unpace and latch
        # the feed's stop flag — the gang completes its current step and
        # exits together (continuous learning has no fixed horizon, so
        # draining a fixed step budget here would be both slow and
        # arbitrary)
        ray_tpu.get(feed.set_pace.remote(0.0), timeout=30.0)
        ray_tpu.get(feed.request_stop.remote(), timeout=30.0)
        fit_th.join(timeout=420)
        assert not fit_th.is_alive(), (
            "trainer did not finish",
            trainer.progress(),
        )
        if "exc" in fit_box:
            raise fit_box["exc"]
        res = fit_box["res"]
        assert res.error is None, res.error
        assert result.ok, result.summary()
        # every fault genuinely fired — a skipped fault would publish a
        # green soak for a scenario that never ran
        for f in result.faults:
            assert not f.detail.startswith("skipped"), (
                f.spec.kind,
                f.detail,
            )
        assert not workload.verify_failures, workload.verify_failures

        # conservation law after the dust settles
        acct = workload.trajectory_accounting()
        assert acct["unaccounted"] == 0, acct
        assert acct["emitted"] > 0

        # loss-curve continuity: the gang's recorded losses equal the
        # shadow's, computed from the identical cached step batches —
        # a reshape that replayed a step with different data would split
        # the curves
        hist = res.metrics_history
        gang_losses = {
            m["step"]: m["loss"]
            for m in hist
            if m.get("loss") == m.get("loss")  # drop NaN (empty steps)
        }
        cache_view = {}
        for m in hist:
            s = m.get("step")
            try:
                blk = ray_tpu.get(
                    feed.take_for_step.remote(s, 4), timeout=30.0
                )
                cache_view[s] = None if blk is None else blk["traj_ids"]
            except Exception as exc:  # noqa: BLE001
                cache_view[s] = repr(exc)
        diag = (
            f"hist={[(m.get('step'), m.get('loss'), m.get('world'), (m.get('traj_ids') or ['-'])[0], m.get('params_finite'), m.get('tok_max')) for m in hist]} "
            f"gang_trained={sorted(gang_losses)} "
            f"shadow_trained={sorted(shadow_losses)} "
            f"cache_view={cache_view} "
            f"pump_errors={pump_errors[:6]} acct={acct}"
        )
        compared = 0
        for s, lv in shadow_losses.items():
            if s in gang_losses:
                assert abs(gang_losses[s] - lv) < 1e-3, (
                    s,
                    gang_losses[s],
                    lv,
                    diag,
                )
                compared += 1
        assert compared >= 5, (
            f"only {compared} overlapping trained steps "
            f"(shadow={len(shadow_losses)}, gang={len(gang_losses)}); "
            + diag
        )

        # the publish fence kept moving through all three fault planes
        # (per-fault convergence was asserted by the orchestrator)
        assert workload.published_epoch() >= 3
    finally:
        stop_evt.set()
        if pump is not None:
            pump.join(timeout=30)
        if workload is not None:
            workload.stop()
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        set_runtime(None)
        try:
            rt.shutdown()
        finally:
            cluster.shutdown()
