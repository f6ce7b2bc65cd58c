"""Sharded parameter-server fleet (docs/PARALLELISM.md "Sharded
parameter-server fleet"): per-shard fan-out, the proto v3 delta wire,
partial-failure semantics, elastic rebalancing, and the fleet-level
acceptance scenarios — all in-process against loopback server groups
(every node a REAL TCP server on its own port), so tier-1 covers the
whole tentpole.
"""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                DataSet, ListDataSetIterator, Sgd)
from deeplearning4j_tpu.monitor import get_flight_recorder, get_registry
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import DistributedMultiLayerNetwork
from deeplearning4j_tpu.parallel.accumulation import (
    EncodedGradientsAccumulator, serialize_encoded)
from deeplearning4j_tpu.paramserver import (
    ParameterServer, ParameterServerClient, ParameterServerTrainingMaster,
    ServerUnavailableError, ShardedParameterServerClient,
    ShardedParameterServerGroup, flatten_params, shard_slice_length)
from deeplearning4j_tpu.paramserver.server import (DELTA_FRAMES, DELTA_FRESH,
                                                   DELTA_FULL)


def _toy_net(seed=11):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=5e-2)).activation("tanh").list()
            .layer(DenseLayer(n_in=6, n_out=16))
            .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _toy_batches(n=8, seed=3):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(16, 6)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)])
            for _ in range(n)]


def _sharded_client(group, **kw):
    kw.setdefault("max_retries", 2)
    kw.setdefault("backoff", 0.01)
    return ShardedParameterServerClient(group.addresses, **kw)


#: the per-training-step wire ops — what "wire bytes per step" means
#: (telemetry/stats/init ride the same series under their own op labels
#: but are join-time or observability traffic, not step traffic)
_STEP_OPS = ("push", "pull", "pull_delta", "version")


def _wire_bytes_total(role="client", ops=_STEP_OPS):
    """Sum of the matching paramserver_wire_bytes_total children (both
    directions) in the global registry — the series the acceptance
    criterion names."""
    fam = get_registry().dump().get("paramserver_wire_bytes_total")
    if not fam:
        return 0.0
    return sum(row["value"] for row in fam["children"]
               if row["labels"].get("role") == role
               and row["labels"].get("op") in ops)


# ------------------------------------------------------------------- group
def test_group_spawns_real_servers_with_round_robin_slices():
    rng = np.random.default_rng(0)
    vec = rng.normal(size=103).astype(np.float32)  # not divisible by 3
    with ShardedParameterServerGroup(3) as group:
        assert len(set(group.addresses)) == 3      # three distinct ports
        with _sharded_client(group) as c:
            c.set_params(vec)
            # each NODE really holds vec[j::3] — proven over the raw wire
            for j, addr in enumerate(group.addresses):
                with ParameterServerClient(addr, max_retries=1,
                                           backoff=0.01) as raw:
                    _, part = raw.pull()
                    np.testing.assert_array_equal(part, vec[j::3])
                    assert part.size == shard_slice_length(j, 103, 3)
            versions, out = c.pull()
            np.testing.assert_array_equal(out, vec)  # reassembly bit-exact
            assert len(set(versions)) == 1


def test_sharded_push_splits_indices_exactly():
    n = 10
    vec = np.arange(n, dtype=np.float32)
    idx = np.array([0, 3, 4, 9], np.int32)   # i % 3 → shards 0, 0, 1, 0
    signs = np.array([1, -1, 1, -1], np.int8)
    with ShardedParameterServerGroup(3) as group:
        with _sharded_client(group) as c:
            c.set_params(vec)
            versions, failed = c.push_encoded((idx, signs, 0.5, n))
            assert failed is None
            exp = vec.copy()
            exp[idx] -= signs * np.float32(0.5)   # applied as p -= decode
            _, out = c.pull()
            np.testing.assert_array_equal(out, exp)
            # only shards that received indices were pushed (0→{0,3,9},
            # 1→{4}; shard 2 got nothing and skipped the round trip)
            assert versions[0] is not None and versions[1] is not None
            assert versions[2] is None


# -------------------------------------------------------------- delta wire
def test_pull_delta_modes_fresh_frames_full():
    rng = np.random.default_rng(1)
    vec = rng.normal(size=64).astype(np.float32)
    frame = serialize_encoded((np.array([2, 7], np.int32),
                               np.array([1, -1], np.int8), 0.25, 64))
    with ParameterServer(port=0, journal=2) as srv:
        with ParameterServerClient(srv.address, max_retries=1,
                                   backoff=0.01) as c:
            assert c.negotiate() >= 3
            v0 = c.set_params(vec)
            # in sync → FRESH
            ver, mode, body = c.pull_delta(v0)
            assert (ver, mode, body) == (v0, DELTA_FRESH, None)
            # one push behind → FRAMES carrying exactly the applied frame
            c.push_update(frame)
            ver, mode, frames = c.pull_delta(v0)
            assert mode == DELTA_FRAMES and ver == v0 + 1
            assert frames == [frame]
            # slack honors the staleness bound without a second round trip
            ver, mode, body = c.pull_delta(v0, slack=1)
            assert mode == DELTA_FRESH
            # journal (maxlen=2) evicted version v0+1 → FULL fallback
            c.push_update(frame)
            c.push_update(frame)
            ver, mode, body = c.pull_delta(v0)
            assert mode == DELTA_FULL
            _, direct = c.pull()
            np.testing.assert_array_equal(body, direct)
            # SET is a barrier: a delta spanning it must go FULL
            v_set = c.set_params(vec)
            c.push_update(frame)
            ver, mode, body = c.pull_delta(v_set - 1)
            assert mode == DELTA_FULL
            # caller AHEAD of the server (restore from older snapshot):
            # forced FULL resync, never a bogus FRESH
            ver, mode, body = c.pull_delta(v_set + 99)
            assert mode == DELTA_FULL


def test_delta_replay_reconstructs_bit_exactly_across_workers():
    """A second worker's pushes arrive as journal frames and replay onto
    the first worker's shadow bit-exactly — the delta wire IS the dense
    pull, minus the bytes."""
    rng = np.random.default_rng(2)
    vec = rng.normal(size=301).astype(np.float32)
    with ShardedParameterServerGroup(3) as group:
        a = _sharded_client(group)
        b = _sharded_client(group)
        try:
            versions = a.set_params(vec)
            for k in range(4):                     # foreign sparse traffic
                idx = rng.choice(301, 17, replace=False).astype(np.int32)
                signs = rng.choice(np.array([-1, 1], np.int8), 17)
                b.push_encoded((idx, np.ascontiguousarray(signs), 1e-2,
                                301))
            got = a.pull_if_stale(versions)
            assert got is not None
            new_versions, payload = got
            assert isinstance(payload, np.ndarray)  # every shard refreshed
            _, dense = b.pull()
            np.testing.assert_array_equal(payload, dense)
            # and the pull rode frames, not full vectors: wire rx for the
            # delta pulls is far below one full vector
            assert a.pull_if_stale(new_versions) is None  # now in sync
        finally:
            a.close()
            b.close()


def test_sharded_delta_training_bit_equivalent_and_2x_fewer_wire_bytes():
    """THE tentpole acceptance: the same fit (same data order, same seeds)
    against one dense server and against a 3-node delta fleet must land
    BIT-EQUIVALENT final params, with the fleet run moving >= 2x fewer
    wire bytes per step (proven via paramserver_wire_bytes_total). The net
    is sized so a full vector dwarfs a sparse frame (a ~1.5k-param toy
    with threshold 1e-2 encodes ~sparse updates; a larger net widens the
    gap)."""
    def _net():
        conf = (NeuralNetConfiguration.builder().seed(21)
                .updater(Sgd(learning_rate=5e-2)).activation("tanh").list()
                .layer(DenseLayer(n_in=12, n_out=96))
                .layer(OutputLayer(n_in=96, n_out=4, activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    def _data():
        rng = np.random.default_rng(17)
        return [DataSet(rng.normal(size=(16, 12)).astype(np.float32),
                        np.eye(4, dtype=np.float32)[
                            rng.integers(0, 4, 16)])
                for _ in range(6)]

    def run(address_or_group, delta):
        net = _net()
        master = (ParameterServerTrainingMaster.Builder(address_or_group)
                  .staleness(0).threshold(1e-2).backoff(0.01)
                  .delta_push(delta).build())
        before = _wire_bytes_total()
        DistributedMultiLayerNetwork(net, master).fit(
            ListDataSetIterator(_data()), epochs=2)
        return net, _wire_bytes_total() - before

    with ParameterServer(port=0) as srv:
        net_dense, wire_dense = run(srv.address, delta=False)
    with ShardedParameterServerGroup(3) as group:
        net_delta, wire_delta = run(group.address, delta=True)

    np.testing.assert_array_equal(flatten_params(net_dense.params),
                                  flatten_params(net_delta.params))
    assert wire_dense >= 2.0 * wire_delta, (wire_dense, wire_delta)


def test_delta_push_residual_rule_matches_dense_server():
    """Numerical equivalence INCLUDING the server-side residual/threshold
    rule: a threshold>0 fleet must accumulate and release sub-threshold
    mass exactly like a dense threshold>0 server fed the same frames —
    which requires empty sub-frames to still reach residual-merging nodes
    (their residual rule runs per push)."""
    n = 12
    rng = np.random.default_rng(5)
    pushes = []
    for _ in range(6):
        k = int(rng.integers(1, 5))
        idx = np.sort(rng.choice(n, k, replace=False)).astype(np.int32)
        signs = rng.choice(np.array([-1, 1], np.int8), k)
        pushes.append((idx, np.ascontiguousarray(signs),
                       float(rng.uniform(0.1, 0.4))))

    with ParameterServer(port=0, threshold=0.5) as srv:
        with ParameterServerClient(srv.address, max_retries=1,
                                   backoff=0.01) as c:
            c.set_params(np.zeros(n, np.float32))
            for idx, signs, thr in pushes:
                c.push_update(serialize_encoded((idx, signs, thr, n)))
            _, dense = c.pull()

    with ShardedParameterServerGroup(3, threshold=0.5) as group:
        with _sharded_client(group) as sc:
            sc.set_params(np.zeros(n, np.float32))
            for idx, signs, thr in pushes:
                versions, failed = sc.push_encoded((idx, signs, thr, n))
                assert failed is None
                # residual-merging nodes see EVERY push, sparse or empty
                assert all(v is not None for v in versions)
            _, sharded = sc.pull()

    np.testing.assert_array_equal(sharded, dense)


def test_v3_client_negotiates_down_against_v2_server():
    """A v3 sharded client against a PR-4-era (proto 2) server must stay
    on the v2 wire for its whole life: no OP_PULL_DELTA ever hits the
    wire (it would be rejected as unknown), pulls fall back to
    version-check + full vector, training still works."""
    import json as _json
    from deeplearning4j_tpu.paramserver.server import OP_PULL_DELTA, OP_STATS

    class _V2Server(ParameterServer):
        def _handle(self, op, payload):
            if op == OP_PULL_DELTA:
                raise ValueError(f"unknown op {op}")
            out = super()._handle(op, payload)
            if op == OP_STATS:
                stats = _json.loads(out.decode("utf-8"))
                stats["proto"] = 2
                out = _json.dumps(stats).encode("utf-8")
            return out

    vec = np.arange(9, dtype=np.float32)
    srv = _V2Server(port=0)
    try:
        c = ShardedParameterServerClient([srv.address], delta=True,
                                         max_retries=1, backoff=0.01)
        try:
            assert c.negotiate() == 2
            versions = c.set_params(vec)
            c.push_encoded((np.array([1], np.int32),
                            np.array([1], np.int8), 0.5, 9))
            got = c.pull_if_stale(versions)
            assert got is not None
            _, payload = got
            exp = vec.copy()
            exp[1] -= 0.5
            np.testing.assert_array_equal(np.asarray(payload), exp)
            # the fallback path never provoked a server error, and the
            # delta op never reached the wire
            with srv._op_lock:
                assert srv._op_counts["pull_delta"] == 0
            assert c.metrics.counters["errors"] == 0
        finally:
            c.close()
    finally:
        srv.stop()


# --------------------------------------------------- partial failure model
def test_dead_shard_fails_per_shard_and_mass_reinjects():
    """One dead node: pushes fail ONLY for its shard (typed per-shard
    ServerUnavailableError inside the client, shard_server_down flight
    event once per transition), the decoded mass comes back for residual
    reinjection, and pulls keep serving the dead shard's shadow — the
    surviving shards never stall."""
    rec = get_flight_recorder()
    rec.clear()
    n = 9
    vec = np.zeros(n, np.float32)
    group = ShardedParameterServerGroup(3)
    try:
        c = _sharded_client(group, max_retries=0, down_backoff=0.2)
        try:
            c.set_params(vec)
            group.kill(1)
            idx = np.array([0, 1, 2], np.int32)    # one index per shard
            signs = np.array([1, 1, 1], np.int8)
            versions, failed = c.push_encoded((idx, signs, 0.5, n))
            assert versions[0] is not None and versions[2] is not None
            assert versions[1] is None
            exp_failed = np.zeros(n, np.float32)
            exp_failed[1] = 0.5                    # shard 1's decoded mass
            np.testing.assert_array_equal(failed, exp_failed)
            # the accumulator re-emits reinjected mass on the next encode
            acc = EncodedGradientsAccumulator(initial_threshold=0.5)
            acc.reinject(failed)
            decoded = acc.store_update(np.zeros(n, np.float32))
            assert np.asarray(decoded)[1] == 0.5
            # degraded pull: survivors fresh, dead shard from shadow
            versions2, out = c.pull()
            exp = vec.copy()
            exp[0] -= 0.5
            exp[2] -= 0.5                          # shard 1 slice unchanged
            np.testing.assert_array_equal(out, exp)
            # down-transition recorded exactly once despite two failing ops
            downs = [e for e in rec.events()
                     if e["event"] == "shard_server_down"]
            assert len(downs) == 1 and downs[0]["shard"] == 1
            # inside the backoff window ops fail fast (no retry burn)
            unavailable = get_registry().counter(
                "paramserver_shard_unavailable_total",
                "per-shard ops lost to a down shard server", role="client",
                shard="1")
            assert unavailable.value >= 2
        finally:
            c.close()
    finally:
        group.stop()


def test_kill_one_shard_server_mid_fit_training_degrades_then_recovers():
    """THE fault acceptance: kill one of three shard nodes mid-fit —
    training neither hangs nor raises (per-shard retries + down-backoff,
    shard_server_down flight event, survivors keep aggregating), and
    after a restart from snapshot the fleet heals (shard_server_restored)
    and convergence resumes."""
    rec = get_flight_recorder()
    rec.clear()
    group = ShardedParameterServerGroup(3)
    try:
        net = _toy_net(seed=5)
        batches = _toy_batches(n=8, seed=2)
        master = ParameterServerTrainingMaster(
            group.address, staleness=0, backoff=0.01, max_retries=1)
        master._ensure_client().down_backoff = 0.2
        killed = {}

        class KillShard:
            def iteration_done(self, model, iteration, score):
                if iteration == 2 and not killed:
                    port, snap = group.kill(1)
                    killed.update(port=port, snap=snap)

        net.set_listeners(KillShard())
        s0 = net.score(DataSet.merge(batches))
        t0 = time.monotonic()
        master.execute_training(net, ListDataSetIterator(batches))
        assert time.monotonic() - t0 < 30.0          # degraded, not hung
        assert killed
        events = [e["event"] for e in rec.events()]
        assert "shard_server_down" in events
        assert np.all(np.isfinite(flatten_params(net.params)))

        # restart from snapshot → the fleet heals and training resumes
        group.restart(1, snapshot=killed["snap"])
        net.listeners = []
        master.execute_training(net, ListDataSetIterator(batches))
        events = [e["event"] for e in rec.events()]
        assert "shard_server_restored" in events
        s1 = net.score(DataSet.merge(batches))
        assert s1 < s0, (s0, s1)                     # convergence resumed
    finally:
        group.stop()


def test_worker_surge_2x_mid_training_neither_halts_nor_corrupts():
    """ROADMAP's elastic target: 3 workers train against the fleet, then a
    2x surge joins mid-training. Nobody halts, nobody corrupts: every
    worker completes, every join is on the flight record, and the merged
    server state stays finite and actually learned."""
    rec = get_flight_recorder()
    rec.clear()
    group = ShardedParameterServerGroup(3)
    errors = []
    first_wave_started = threading.Event()

    def worker(wid, seed):
        try:
            master = ParameterServerTrainingMaster(
                group.address, staleness=1, backoff=0.01,
                worker_id=f"surge-{wid}", telemetry_interval=None)
            net = _toy_net(seed=seed)
            first_wave_started.set()
            # n=16: enough async quantized steps that the learned-state
            # probe below clears its margin on every scheduler
            # interleaving — n=6 left it within noise of random init
            # (flaky under load, ~1-in-3 on a busy CI box)
            master.execute_training(
                net, ListDataSetIterator(_toy_batches(n=16, seed=seed)))
        except Exception as e:  # noqa: BLE001 - surfaced via errors below
            errors.append((wid, e))

    try:
        first = [threading.Thread(target=worker, args=(i, 30 + i),
                                  daemon=True) for i in range(3)]
        for t in first:
            t.start()
        first_wave_started.wait(timeout=30)
        surge = [threading.Thread(target=worker, args=(i, 40 + i),
                                  daemon=True) for i in range(3, 6)]
        for t in surge:
            t.start()
        for t in first + surge:
            t.join(timeout=120)
            assert not t.is_alive(), "worker hung"
        assert errors == []
        joins = {e["worker"] for e in rec.events()
                 if e["event"] == "worker_join"}
        assert {f"surge-{i}" for i in range(6)} <= joins
        with _sharded_client(group) as c:
            _, merged = c.pull()
        assert np.all(np.isfinite(merged))
        # the merged state trained: a fresh net adopting it scores better
        # than a fresh net's own random init
        probe = _toy_net(seed=50)
        batches = _toy_batches(n=6, seed=30)
        s_random = probe.score(DataSet.merge(batches))
        from deeplearning4j_tpu.paramserver import set_params_from_flat
        set_params_from_flat(probe, merged)
        s_trained = probe.score(DataSet.merge(batches))
        assert s_trained < s_random, (s_random, s_trained)
    finally:
        group.stop()


# ----------------------------------------------------------------- elastic
def test_scale_to_rebalances_state_and_clients_remap():
    """Rebalance runbook: scale_to(m) re-splits values AND residuals onto
    the new layout bit-preservingly; remapped clients resync in full; the
    flight record carries join/leave/rebalance/remap audit events."""
    rec = get_flight_recorder()
    rec.clear()
    rng = np.random.default_rng(9)
    vec = rng.normal(size=97).astype(np.float32)
    group = ShardedParameterServerGroup(2, threshold=0.5)
    try:
        c = _sharded_client(group)
        try:
            c.set_params(vec)
            # leave sub-threshold residual mass behind on the old layout
            c.push_encoded((np.array([0], np.int32),
                            np.array([1], np.int8), 0.2, 97))
            addrs = group.scale_to(3)
            assert len(addrs) == 3
            c.remap(addrs)
            _, out = c.pull()
            np.testing.assert_array_equal(out, vec)   # values preserved
            # the residual moved with the reshard: two more 0.2 pushes
            # cross the 0.5 threshold exactly as on a single server
            c.push_encoded((np.array([0], np.int32),
                            np.array([1], np.int8), 0.2, 97))
            c.push_encoded((np.array([0], np.int32),
                            np.array([1], np.int8), 0.2, 97))
            _, out = c.pull()
            exp = vec.copy()
            exp[0] -= 0.5
            np.testing.assert_array_equal(out, exp)
            events = [e["event"] for e in rec.events()]
            assert "shard_server_join" in events
            assert "shard_group_rebalance" in events
            assert "client_remap" in events
        finally:
            c.close()

    finally:
        group.stop()

    # master-level runbook step (fresh fleet seeded by the net itself):
    # fit → scale the group → master.remap → refit re-joins and adopts
    # the rebalanced state
    with ShardedParameterServerGroup(2) as group2:
        net = _toy_net(seed=3)
        master = ParameterServerTrainingMaster(group2.address,
                                               backoff=0.01)
        master.execute_training(net,
                                ListDataSetIterator(_toy_batches(n=2)))
        addrs = group2.scale_to(3)
        master.remap(addrs)
        master.execute_training(net,
                                ListDataSetIterator(_toy_batches(n=2)))
        assert master.client.num_servers == 3
        # scale DOWN folds shards back losslessly too
        addrs = group2.scale_to(2)
        master.remap(addrs)
        master.execute_training(net,
                                ListDataSetIterator(_toy_batches(n=2)))
        events = [e["event"] for e in rec.events()]
        assert "shard_server_leave" in events


# ------------------------------------------------- shared fan-out satellite
def test_single_server_parallel_shard_pulls_share_fanout_path():
    """Satellite: per-shard pulls parallelize even against ONE server —
    pull_sharded rides the same Fanout/connection-pool machinery as the
    fleet client and reassembles bit-exactly; the pool really held
    multiple live sockets (the parallelism evidence). No shard pull hands
    its socket back before all four hold one, so the overlap does not
    depend on thread timing: a fan-out that ran the pulls one after
    another would break the barrier instead."""
    rng = np.random.default_rng(4)
    vec = rng.normal(size=205).astype(np.float32)
    with ParameterServer(port=0, num_shards=4) as srv:
        with ParameterServerClient(srv.address, pool_size=4,
                                   max_retries=1, backoff=0.01) as c:
            c.set_params(vec)
            together = threading.Barrier(4, timeout=30)
            in_pull = threading.local()
            pull, checkin = c.pull, c._checkin

            def pull_holding(shard=-1):
                in_pull.on = True
                try:
                    return pull(shard=shard)
                finally:
                    in_pull.on = False

            def checkin_together(s):
                if getattr(in_pull, "on", False):
                    together.wait()
                checkin(s)

            c.pull, c._checkin = pull_holding, checkin_together
            version, out = c.pull_sharded()
            np.testing.assert_array_equal(out, vec)
            assert version == c.server_version()[0]
            with c._pool_lock:
                assert len(c._pool) >= 2   # concurrent checkouts happened


def test_sharded_client_single_address_is_the_legacy_path_plus_delta():
    """delta_push(True) with ONE address rides the sharded client (one
    code path) against a single server: delta pulls, same results as the
    plain client."""
    net = _toy_net(seed=8)
    batches = _toy_batches(n=4, seed=6)
    with ParameterServer(port=0) as srv:
        master = (ParameterServerTrainingMaster.Builder(srv.address)
                  .staleness(0).backoff(0.01).delta_push(True).build())
        DistributedMultiLayerNetwork(net, master).fit(
            ListDataSetIterator(batches))
        assert isinstance(master.client, ShardedParameterServerClient)
        assert master.client.num_servers == 1
        # the fit's resyncs really rode the delta op, not full pulls
        with srv._op_lock:
            assert srv._op_counts["pull_delta"] >= len(batches)
            assert srv._op_counts["pull"] <= 1   # at most the join pull


def test_builder_num_servers_cross_checks_addresses():
    with pytest.raises(ValueError, match="num_servers"):
        (ParameterServerTrainingMaster.Builder("127.0.0.1:1,127.0.0.1:2")
         .num_servers(3).build())._ensure_client()


def test_init_requires_whole_fleet():
    """A down shard at JOIN time raises (a partial seed would strand mixed
    state) — unlike mid-training ops, which degrade per shard."""
    group = ShardedParameterServerGroup(3)
    try:
        group.kill(2)
        c = _sharded_client(group, max_retries=0)
        try:
            with pytest.raises(ServerUnavailableError, match="shard 2"):
                c.init_params(np.zeros(6, np.float32))
        finally:
            c.close()
    finally:
        group.stop()
