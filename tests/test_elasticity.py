"""Elastic membership for the async-SSP tier (ISSUE 6).

PR 1 made the tier survive failures (eviction, reconnect, rejoin); these
tests pin the other half — the member set CHANGING under a live job:

- admit: a worker id outside the launch roster joins at the service-picked
  rendezvous anchor clock, pulls anchor + clock table, and its pushes ride
  the same exactly-once seq dedup as everyone else's;
- retire: a deliberate departure removes the slot from every gate's
  denominator (eviction only excludes; retirement removes);
- the acceptance chaos scenario: a FaultProxy-backed 1 -> 3 -> 2 scale
  sequence with loss continuity, every clock applied exactly once, no SSP
  gate deadlock across membership changes, and the final anchor BITWISE
  equal to a fixed-membership run of the same dispatched step sequence;
- resharded data assignment keyed by (member list, epoch);
- fast restart: persistent compile cache + the AOT step-executable store
  that make elasticity cheap.

Everything socket-level is deterministic: port-0 loopback binds, explicit
clock orchestration from the test thread (no wall-clock races decide which
clocks land), deltas that are distinct powers of two so the anchor SUM is
a bit-exact record of exactly which (worker, clock) increments applied —
a duplicate or dropped apply cannot hide.
"""

import os
import socket
import threading
import time

import numpy as np
import pytest

from poseidon_tpu.data.workload import (Shard, elastic_shard_indices,
                                        member_shard)
from poseidon_tpu.parallel.async_ssp import (AsyncSSPClient, ParamService,
                                             run_async_ssp_worker)
from poseidon_tpu.runtime.faults import FaultProxy, FaultRule

# tight knobs so every reconnect/eviction resolves in test time
FAST = dict(heartbeat_s=0.1, reconnect_deadline_s=5.0,
            backoff_base_s=0.01, backoff_cap_s=0.1)


def _zeros64(shape=(2, 2)):
    # float64 anchor: sums of DISTINCT powers of two (the test deltas) are
    # exact for exponents spanning < 53 bits, so the final anchor is a
    # bit-exact set-membership record of applied (worker, clock) pairs
    return {"fc": {"w": np.zeros(shape, np.float64)}}


def _delta(w, c, shape=(2, 2)):
    """The (worker, clock) increment: a unique power of two per pair."""
    assert 0 <= c < 16 and 0 <= w < 3
    return {"fc": {"w": np.full(shape, 2.0 ** (w * 16 + c), np.float64)}}


def _expected(pairs, shape=(2, 2)):
    total = sum(2.0 ** (w * 16 + c) for w, c in pairs)
    return np.full(shape, total, np.float64)


def _wait_for(pred, timeout_s=10.0, what="condition"):
    deadline = time.time() + timeout_s
    while not pred():
        if time.time() > deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.01)


# --------------------------------------------------------------------------- #
# admit: rendezvous at the anchor clock
# --------------------------------------------------------------------------- #

def test_admit_new_worker_joins_at_anchor_clock():
    """A worker id outside n_workers joins mid-run: the service picks the
    join clock (min applied clock over live members), hands back anchor +
    clocks + member list, and the joiner's pushes apply exactly once from
    join_clock + 1. Both sides' gates run over the grown member set."""
    svc = ParamService(_zeros64(), n_workers=1, liveness_timeout_s=0.0)
    cli0 = AsyncSSPClient(0, ("127.0.0.1", svc.port), staleness=1,
                          n_workers=1, **FAST)
    cli1 = None
    try:
        for c in range(3):
            cli0.gate(c, timeout_s=10.0)
            cli0.push(_delta(0, c))
        cli0._drain()
        assert svc.clocks[0] == 2

        cli1 = AsyncSSPClient(1, ("127.0.0.1", svc.port), staleness=1,
                              n_workers=1, **FAST)
        cache, clocks = cli1.join()
        # rendezvous anchor clock = min live clock = w0's clock
        assert cli1.clock == 2 and cli1._acked_clock == 2
        assert clocks[1] == 2
        assert cli1.members == {0, 1}
        assert svc.members == {0, 1}
        assert svc.admissions == 1
        # the joiner's cache is the anchor: every applied increment visible
        np.testing.assert_array_equal(
            cache["fc"]["w"], _expected([(0, 0), (0, 1), (0, 2)]))

        # joiner contributes from join_clock + 1; exactly-once
        cli1.gate(3, timeout_s=10.0)
        cli1.push(_delta(1, 3))
        cli1._drain()
        assert svc.clocks[1] == 3 and svc.applied_seq[1] == 3
        np.testing.assert_array_equal(
            svc.anchor["fc"]["w"],
            _expected([(0, 0), (0, 1), (0, 2), (1, 3)]))

        # w0's next ack folds the new member into its gate view
        cli0.push(_delta(0, 3))
        cli0._drain()
        assert cli0.members == {0, 1}
        # gate within the window returns immediately for both
        assert cli0.gate(4, timeout_s=10.0) == 0.0
        assert cli1.gate(4, timeout_s=10.0) == 0.0
    finally:
        for c in (cli0, cli1):
            if c is not None:
                c.close()
        svc.close()


def test_admit_is_idempotent_for_existing_member():
    """join() by an id that is already a member degenerates to the rejoin
    pull: resume at the applied clock, no admissions bump — one join path
    serves fresh workers, restarts, and true admissions alike."""
    svc = ParamService(_zeros64(), n_workers=2, liveness_timeout_s=0.0)
    cli0 = AsyncSSPClient(0, ("127.0.0.1", svc.port), staleness=0,
                          n_workers=2, **FAST)
    try:
        cli0.push(_delta(0, 0))
        cli0._drain()
        cache, clocks = cli0.join()
        assert cli0.clock == 0 and cli0._acked_clock == 0
        assert svc.admissions == 0
        assert svc.members == {0, 1}
        np.testing.assert_array_equal(cache["fc"]["w"],
                                      _expected([(0, 0)]))
    finally:
        cli0.close()
        svc.close()


def test_readmitted_id_resumes_past_its_seq_high_water_mark():
    """A previously retired id that is admitted again must resume its
    push-seq stream PAST everything it ever flushed — otherwise the
    exactly-once dedup would swallow its post-readmission flushes (the
    healthy-looking-but-contributing-nothing failure mode)."""
    svc = ParamService(_zeros64(), n_workers=2, liveness_timeout_s=0.0)
    cli1 = AsyncSSPClient(1, ("127.0.0.1", svc.port), staleness=0,
                          n_workers=2, **FAST)
    try:
        for c in range(5):
            cli1.push(_delta(1, c))
        cli1.leave()
        assert svc.members == {0} and svc.retired == {1}
        cli1.close()

        # the same id comes back while the fleet idles at lower clocks
        cli1 = AsyncSSPClient(1, ("127.0.0.1", svc.port), staleness=0,
                              n_workers=2, **FAST)
        cli1.join()
        # NOT the anchor min (worker 0 sits at -1): its own high-water mark
        assert cli1.clock == 4
        cli1.push(_delta(1, 5))
        cli1._drain()
        assert svc.applied_seq[1] == 5  # applied, not swallowed
        np.testing.assert_array_equal(
            svc.anchor["fc"]["w"],
            _expected([(1, c) for c in range(6)]))
    finally:
        cli1.close()
        svc.close()


# --------------------------------------------------------------------------- #
# retire: the slot leaves the gates
# --------------------------------------------------------------------------- #

def test_retire_removes_slot_from_gates():
    """After a deliberate departure the survivor's gate stops counting the
    retired slot IMMEDIATELY (no liveness timeout involved): a gate that
    the retired worker's frozen clock would violate unblocks as soon as
    the survivor's poll sees the shrunken member list."""
    svc = ParamService(_zeros64(), n_workers=2, liveness_timeout_s=0.0)
    cli0 = AsyncSSPClient(0, ("127.0.0.1", svc.port), staleness=0,
                          n_workers=2, **FAST)
    cli1 = AsyncSSPClient(1, ("127.0.0.1", svc.port), staleness=0,
                          n_workers=2, **FAST)
    try:
        cli1.push(_delta(1, 0))
        cli1.leave()   # drains, then retires the slot
        assert svc.retired == {1} and svc.members == {0}

        for c in range(4):
            cli0.push(_delta(0, c))
        # s=0, clock 4: needs every OTHER member at >= 3; worker 1 is
        # frozen at 0, so pre-retire this would block to the timeout
        waited = cli0.gate(4, poll_s=0.01, timeout_s=5.0)
        assert waited < 2.0, f"gate did not unblock on retirement: {waited}"
        assert 1 not in cli0.members
    finally:
        cli0.close()
        cli1.close()
        svc.close()


# --------------------------------------------------------------------------- #
# one-shot nth fault rule
# --------------------------------------------------------------------------- #

def test_one_shot_nth_rule_fires_on_exactly_the_nth_match():
    """FaultRule(nth=N) fires on exactly the Nth connection passing its
    filters, then expires: earlier connections pass untouched, later ones
    too — the targeting primitive count-based rules cannot express."""
    # minimal echo upstream
    srv = socket.create_server(("127.0.0.1", 0))
    stop = threading.Event()

    def echo_loop():
        srv.settimeout(0.1)
        while not stop.is_set():
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return

            def pump(c):
                try:
                    while True:
                        d = c.recv(1024)
                        if not d:
                            return
                        c.sendall(d)
                except OSError:
                    pass
                finally:
                    c.close()

            threading.Thread(target=pump, args=(conn,), daemon=True).start()

    threading.Thread(target=echo_loop, daemon=True).start()
    proxy = FaultProxy(srv.getsockname())
    rule = proxy.add_rule(FaultRule(action="drop", nth=2))
    try:
        outcomes = []
        for i in range(5):
            sk = socket.create_connection(proxy.addr, timeout=5.0)
            try:
                sk.sendall(b"ping")
                sk.settimeout(2.0)
                outcomes.append(sk.recv(4) == b"ping")
            except OSError:
                outcomes.append(False)
            finally:
                sk.close()
        # exactly the 3rd (0-based nth=2) connection died
        assert outcomes == [True, True, False, True, True], outcomes
        assert rule.expired and rule.hits == 1
        assert proxy.dropped == 1
    finally:
        stop.set()
        proxy.close()
        srv.close()


def test_one_shot_nth_rule_kills_admit_handshake_specifically():
    """Target the rejoin/admit handshake: after a partition, the FIRST
    redial carries the admit rendezvous — nth selects exactly it (the
    client's earlier setup dials already consumed indices 0 and 1, which
    conn=/max_conns= rules would need to predict). The client's backoff
    absorbs the kill and the admission still lands exactly once."""
    svc = ParamService(_zeros64(), n_workers=1, liveness_timeout_s=0.0)
    proxy = FaultProxy(("127.0.0.1", svc.port))
    # heartbeats off: the only post-sever connection is join()'s redial,
    # so the accepted-connection order is fully deterministic
    opts = dict(FAST, heartbeat_s=0.0)
    cli1 = AsyncSSPClient(1, proxy.addr, staleness=1, n_workers=1, **opts)
    try:
        rule = proxy.add_rule(FaultRule(action="drop", nth=0))
        # rule armed AFTER setup: nth counts from here — the next dial IS
        # the admit handshake's reconnect
        assert proxy.sever_all() == 2
        cache, _ = cli1.join()   # pull channel dead -> redial (killed once)
        assert rule.expired and proxy.dropped == 1
        assert svc.admissions == 1          # exactly once, despite the kill
        assert svc.members == {0, 1}
        assert cli1.clock == -1             # fresh job: anchor clock -1
        cli1.push(_delta(1, 0))
        cli1._drain()
        assert svc.applied_seq[1] == 0
    finally:
        cli1.close()
        proxy.close()
        svc.close()


# --------------------------------------------------------------------------- #
# THE acceptance scenario: 1 -> 3 -> 2 under chaos
# --------------------------------------------------------------------------- #

def test_chaos_scale_1_3_2_exactly_once_with_fixed_membership_replay():
    """Scale a live async-SSP job 1 -> 3 -> 2 through the FaultProxy, with
    a one-shot nth kill of worker 2's first dial and a full mid-run
    partition (sever_all) thrown in. Acceptance properties, all pinned
    bit-exactly because every (worker, clock) delta is a distinct power
    of two:

    - every clock applied exactly once (the anchor sum IS the applied
      set; a dup or drop changes it);
    - no SSP gate deadlock across admissions and the retirement (every
      gate completes within its timeout, and the post-shrink gates that
      worker 1's frozen clock WOULD have violated unblock);
    - loss continuity: each worker's per-clock losses are the expected
      unbroken sequence across every membership change;
    - final params identical to a fixed-membership (3-worker) service fed
      the same dispatched step sequence — bitwise."""
    s = 2
    svc = ParamService(_zeros64(), n_workers=1, liveness_timeout_s=0.0)
    proxy = FaultProxy(("127.0.0.1", svc.port))
    losses = {0: [], 1: [], 2: []}
    clis = {}

    def step(w, cli, c):
        """One clock for worker w: gate, 'train' (record the loss), push
        the (w, c) increment. Returns the gate wait."""
        waited = cli.gate(c, timeout_s=20.0)
        losses[w].append(float(c))     # deterministic 'loss' = the clock
        cli.push(_delta(w, c))
        return waited

    try:
        # ---- phase 1: one worker, clocks 0..3 --------------------------- #
        clis[0] = AsyncSSPClient(0, proxy.addr, staleness=s, n_workers=1,
                                 **FAST)
        for c in range(4):
            step(0, clis[0], c)
        clis[0]._drain()

        # ---- scale up 1 -> 3: admit w1 then w2 -------------------------- #
        clis[1] = AsyncSSPClient(1, proxy.addr, staleness=s, n_workers=1,
                                 **FAST)
        cache1, _ = clis[1].join()
        assert clis[1].clock == 3                 # the anchor clock
        np.testing.assert_array_equal(
            cache1["fc"]["w"], _expected([(0, c) for c in range(4)]))

        # chaos: kill w2's very first dial (its next accepted connection)
        kill = proxy.add_rule(FaultRule(action="drop", nth=0))
        clis[2] = AsyncSSPClient(2, proxy.addr, staleness=s, n_workers=1,
                                 **FAST)
        cache2, _ = clis[2].join()
        assert kill.expired and proxy.dropped >= 1
        assert clis[2].clock == 3
        assert svc.admissions == 2
        assert svc.members == {0, 1, 2}

        # ---- phase 2: three workers, clocks 4..6 ------------------------ #
        for c in range(4, 7):
            for w in (0, 1, 2):
                step(w, clis[w], c)
            if c == 5:
                # chaos: full mid-run partition; every channel reconnects
                # and replays, the seq dedup keeps the applied set exact
                proxy.sever_all()

        # ---- scale down 3 -> 2: w1 departs deliberately ----------------- #
        clis[1].leave()
        assert svc.retired == {1}
        assert svc.members == {0, 2}

        # ---- phase 3: two workers, clocks 7..11 ------------------------- #
        # w1 froze at clock 6; by clock 10 (> 6 + s + 1) its slot would
        # deadlock every gate were it still a member
        for c in range(7, 12):
            for w in (0, 2):
                waited = step(w, clis[w], c)
                assert waited < 15.0
        clis[0].mark_done()
        clis[2].mark_done()

        # ---- acceptance: exactly-once, spread bound, loss continuity ---- #
        applied = ([(0, c) for c in range(12)]
                   + [(1, c) for c in range(4, 7)]
                   + [(2, c) for c in range(4, 12)])
        np.testing.assert_array_equal(svc.anchor["fc"]["w"],
                                      _expected(applied))
        assert svc.max_spread <= s + 1
        assert losses[0] == [float(c) for c in range(12)]
        assert losses[1] == [4.0, 5.0, 6.0]
        assert losses[2] == [float(c) for c in range(4, 12)]
        done, failed = clis[0].wait_all_done(None, timeout_s=10.0)
        assert done == {0, 2} and not failed

        # ---- fixed-membership replay of the same dispatched sequence ---- #
        svc2 = ParamService(_zeros64(), n_workers=3, liveness_timeout_s=0.0)
        replay = {w: AsyncSSPClient(w, ("127.0.0.1", svc2.port), staleness=s,
                                    n_workers=3, **FAST) for w in (0, 1, 2)}
        try:
            for w, cli in replay.items():
                start = {0: 0, 1: 4, 2: 4}[w]
                cli.clock = start - 1
                cli._acked_clock = start - 1
                end = {0: 12, 1: 7, 2: 12}[w]
                for c in range(start, end):
                    cli.push(_delta(w, c))
                cli._drain()
            np.testing.assert_array_equal(svc2.anchor["fc"]["w"],
                                          svc.anchor["fc"]["w"])
        finally:
            for cli in replay.values():
                cli.close()
            svc2.close()
    finally:
        for cli in clis.values():
            cli.close()
        proxy.close()
        svc.close()


def test_worker_driver_join_and_retire_modes():
    """run_async_ssp_worker's elastic modes: join=True rendezvous via
    admit and trains from join_clock + 1; retire_at_clock scales down
    cleanly (drain + retire, survivors keep training)."""
    svc = ParamService(_zeros64(), n_workers=1, liveness_timeout_s=0.0)
    opts = dict(heartbeat_s=0.1, reconnect_deadline_s=5.0,
                backoff_base_s=0.01, backoff_cap_s=0.05)

    def local_step(w):
        def f(cache, it):
            out = {l: {p: v + _delta(w, it % 16)[l][p] for p, v in
                       ps.items()} for l, ps in cache.items()}
            return out, float(it)
        return f

    cli0 = AsyncSSPClient(0, ("127.0.0.1", svc.port), staleness=4,
                          n_workers=1, **opts)
    try:
        for c in range(3):
            cli0.gate(c, timeout_s=10.0)
            cli0.push(_delta(0, c))
        cli0._drain()

        out = run_async_ssp_worker(
            1, 1, _zeros64(), local_step(1), n_clocks=7, staleness=4,
            service_addr=("127.0.0.1", svc.port), join=True,
            retire_at_clock=5, client_opts=opts)
        # joined at anchor clock 2 -> trained clocks 3..5, then retired
        assert out["start_clock"] == 3
        assert out["retired"] is True
        assert out["losses"] == [3.0, 4.0, 5.0]
        assert svc.retired == {1} and svc.members == {0}
        np.testing.assert_array_equal(
            svc.anchor["fc"]["w"],
            _expected([(0, 0), (0, 1), (0, 2),
                       (1, 3), (1, 4), (1, 5)]))
        cli0.mark_done()
    finally:
        cli0.close()
        svc.close()


# --------------------------------------------------------------------------- #
# resharded data assignment: keyed by (member list, epoch)
# --------------------------------------------------------------------------- #

def test_elastic_shard_partitions_cleanly_across_1_3_2():
    """For every membership of a 1 -> 3 -> 2 scale sequence the shards are
    disjoint and cover [0, n); the epoch permutation is shared (keyed by
    epoch, membership-independent), so a scale event re-cuts the SAME
    permutation into the new number of ranges."""
    n = 101
    for members in ([0], [0, 1, 2], [0, 2]):
        for epoch in (0, 3):
            parts = [elastic_shard_indices(n, w, members, epoch=epoch)
                     for w in members]
            flat = np.concatenate(parts)
            assert len(flat) == n
            assert set(flat.tolist()) == set(range(n)), \
                f"members={members} epoch={epoch} does not cover [0, n)"
    # position-in-sorted-list mapping: worker 2 is the SECOND of {0, 2}
    assert member_shard([0, 2], 2) == Shard(1, 2)
    assert member_shard([0, 1, 2], 1) == Shard(1, 3)
    # membership sets (not launch ranks) key the cut: {5, 9} works too
    assert member_shard({9, 5}, 9) == Shard(1, 2)
    with pytest.raises(ValueError):
        member_shard([0, 2], 1)
    # epoch keying: different epochs permute differently, same cover
    e0 = elastic_shard_indices(n, 0, [0, 1], epoch=0)
    e1 = elastic_shard_indices(n, 0, [0, 1], epoch=1)
    assert not np.array_equal(e0, e1)


# --------------------------------------------------------------------------- #
# membership telemetry export
# --------------------------------------------------------------------------- #

def test_membership_counters_export_and_format():
    """ParamService churn counters surface through comm_stats (the
    engine's display + stats.yaml path) — no log-grepping required."""
    from poseidon_tpu.runtime.comm_stats import (format_membership,
                                                 membership_counters)

    svc = ParamService(_zeros64(), n_workers=1, liveness_timeout_s=0.0)
    cli1 = AsyncSSPClient(1, ("127.0.0.1", svc.port), staleness=0,
                          n_workers=1, **FAST)
    try:
        cli1.join()
        c = membership_counters(service=svc)
        assert c["admissions"] == 1.0
        assert c["members"] == 2.0
        assert c["evictions"] == 0.0 and c["rejoins"] == 0.0
        assert c["retired"] == 0.0
        line = format_membership(c)
        assert "admissions = 1" in line and "members = 2" in line

        cli1.leave()
        c = membership_counters(service=svc)
        assert c["members"] == 1.0 and c["retired"] == 1.0

        # client-side view (every non-zero rank)
        cc = membership_counters(client=cli1)
        assert cc["members"] == 1.0 and "reconnects" in cc
    finally:
        cli1.close()
        svc.close()


# --------------------------------------------------------------------------- #
# engine/tier integration (jax, CPU)
# --------------------------------------------------------------------------- #

_SMALLNET = """
name: "ElasticNet"
layers { name: "src" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 1 height: 12 width: 12 } }
layers { name: "ip1" type: INNER_PRODUCT bottom: "data" top: "ip1"
  inner_product_param { num_output: 5
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""


def _memory_data(n=64, seed=0):
    rs = np.random.RandomState(seed)
    return {"data": rs.randn(n, 1, 12, 12).astype(np.float32),
            "label": rs.randint(0, 5, n)}


# the small net with a DROPOUT layer: a step that READS its ``rng`` argument
_DROPNET = _SMALLNET.replace(
    'layers { name: "loss"',
    'layers { name: "drop1" type: DROPOUT bottom: "ip1" top: "ip1"\n'
    '  dropout_param { dropout_ratio: 0.25 } }\n'
    'layers { name: "loss"')


def _small_engine(tmp_path, net=_SMALLNET, solver=None, **kw):
    """``solver``: SolverParameter fields over the defaults below; the
    other keywords are the Engine's."""
    from poseidon_tpu.proto.messages import (SolverParameter,
                                             load_net_from_string)
    from poseidon_tpu.runtime.engine import Engine
    fields = dict(base_lr=0.01, lr_policy="fixed", momentum=0.9, display=0,
                  max_iter=kw.pop("max_iter", 4), random_seed=3)
    fields.update(solver or {})
    sp = SolverParameter(train_net_param=load_net_from_string(net), **fields)
    return Engine(sp, memory_data=_memory_data(),
                  output_dir=str(tmp_path), **kw)


def test_engine_reshard_data_rebuilds_pipelines(tmp_path):
    """reshard_data re-keys the TRAIN assignment mid-run: pipelines are
    rebuilt against the new contiguous range and training keeps going."""
    eng = _small_engine(tmp_path, max_iter=2)
    try:
        eng.train()
        old_pipes = list(eng.train_pipelines)
        assert eng._data_shard == Shard(0, 1)
        assert eng.reshard_data(Shard(0, 2)) is True
        assert eng._data_shard == Shard(0, 2)
        assert eng.train_pipelines[0] is not old_pipes[0]
        assert eng.reshard_data(Shard(0, 2)) is False   # no-op on same
        eng.train(max_iter=4)   # two more iterations on the new shard
        assert eng.iteration() == 4
    finally:
        eng.close()


def test_tier_membership_change_reshards_engine(tmp_path, monkeypatch):
    """The product seam: an admission lands, and the NEXT flush boundary
    reshards the engine's data assignment by the grown member list."""
    import types

    from poseidon_tpu.runtime.async_tier import AsyncSSPTier

    monkeypatch.setenv("POSEIDON_PROC_ID", "0")
    monkeypatch.setenv("POSEIDON_NUM_PROCS", "1")
    monkeypatch.delenv("POSEIDON_COORDINATOR", raising=False)

    params = _zeros64()
    resharded = []
    eng = types.SimpleNamespace()
    eng.params = params
    eng.train_step = types.SimpleNamespace(replicated=None)
    eng.reshard_data = lambda shard: resharded.append(shard)

    tier = AsyncSSPTier(params, staleness=50, service_port=0)
    joiner = None
    try:
        assert tier.data_shard() == Shard(0, 1)
        # a new worker joins the live job
        joiner = AsyncSSPClient(1, ("127.0.0.1", tier.service.port),
                                staleness=50, n_workers=1, **FAST)
        joiner.join()
        assert tier.service.admissions == 1
        # next flush boundary: the tier folds the admission into the shard
        tier.after_iters(eng, 1)
        assert resharded and resharded[-1] == Shard(0, 2)
        assert tier.membership_counters()["admissions"] == 1.0
        # the joiner departs; the next boundary re-cuts back to one range
        joiner.leave()
        tier.after_iters(eng, 1)
        assert resharded[-1] == Shard(0, 1)
        tier.finish(eng)
    finally:
        if joiner is not None:
            joiner.close()
        if tier.service is not None:
            tier.service.close()


def test_joiner_tier_is_admitted_without_operator_action(monkeypatch):
    """A process launched with POSEIDON_PROC_ID >= POSEIDON_NUM_PROCS (the
    elastic-joiner env contract) builds its tier, is ADMITTED at the
    anchor clock, and computes its member-keyed data shard — no relaunch
    of the fleet, no new hostfile."""
    import types

    from poseidon_tpu.runtime.async_tier import AsyncSSPTier

    params = _zeros64()
    svc = ParamService(params, n_workers=2, liveness_timeout_s=0.0)
    cli0 = AsyncSSPClient(0, ("127.0.0.1", svc.port), staleness=50,
                          n_workers=2, **FAST)
    tier = None
    try:
        cli0.push(_delta(0, 0))
        cli0._drain()

        monkeypatch.setenv("POSEIDON_PROC_ID", "2")
        monkeypatch.setenv("POSEIDON_NUM_PROCS", "2")
        monkeypatch.delenv("POSEIDON_COORDINATOR", raising=False)
        tier = AsyncSSPTier(params, staleness=50, service_port=svc.port)
        assert svc.admissions == 1
        assert svc.members == {0, 1, 2}
        # admitted at the anchor clock (min live = worker 1's -1)
        assert tier.client.clock == -1
        # the anchor seeded the joiner's cache
        np.testing.assert_array_equal(tier.resume_cache["fc"]["w"],
                                      _expected([(0, 0)]))
        assert tier.data_shard() == Shard(2, 3)

        eng = types.SimpleNamespace()
        eng.params = tier.resume_cache
        eng.train_step = types.SimpleNamespace(replicated=None)
        tier.after_iters(eng, 1)    # first flush from the admitted worker
        tier.client._drain()
        assert svc.applied_seq[2] == 0
    finally:
        if tier is not None:
            tier.client.close()
        cli0.close()
        svc.close()


# --------------------------------------------------------------------------- #
# fast restart: compile cache + AOT step store
# --------------------------------------------------------------------------- #

def test_cache_dir_from_env_is_never_overridden_in_code(jax_cache_env,
                                                        monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: that directory IS the cache — the
    program never points jax at another one, its aot/ store lives under
    it, and compiles land in it."""
    import jax
    import jax.numpy as jnp

    from poseidon_tpu import config
    from poseidon_tpu.runtime import compile_cache as cc

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    cache = cc.enable_compile_cache()
    assert cache == jax_cache_env == cc.resolve_cache_dir()
    assert "jax_compilation_cache_dir" not in updates
    assert config.compile_cache_config().cache_dir == cache
    assert cc._aot_path(cache, "k").startswith(
        os.path.join(cache, "aot") + os.sep)
    before = cc.cache_entries(cache)
    x = jnp.ones((16, 16))
    jax.block_until_ready(jax.jit(lambda a: jnp.tanh(a) @ a.T)(x))
    assert cc.cache_entries(cache) > before, \
        "the persistent cache recorded no entry for a fresh compile"


def test_cache_dir_defaults_to_checkout(monkeypatch):
    """Unset: a fixed <checkout>/.jax_cache resolved from __file__ — never
    tempfile, a pid, the clock or ~/.cache — for every store."""
    from poseidon_tpu.runtime import compile_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.resolve_cache_dir() == os.path.join(repo, ".jax_cache")
    assert cc.resolve_cache_dir() == cc.resolve_cache_dir()


def test_step_key_stability_and_sensitivity():
    from poseidon_tpu.runtime.compile_cache import step_key

    a = step_key(model="lenet", batch={"data": ([8, 1, 12, 12], "float32")},
                 mesh={"data": 8}, backend="cpu")
    b = step_key(mesh={"data": 8}, backend="cpu", model="lenet",
                 batch={"data": ([8, 1, 12, 12], "float32")})
    assert a == b, "kwargs order must not change the key"
    c = step_key(model="lenet", batch={"data": ([16, 1, 12, 12], "float32")},
                 mesh={"data": 8}, backend="cpu")
    assert a != c, "a shape change must miss"


def test_aot_step_store_roundtrip_bitwise(tmp_path):
    """A serialized train-step executable reloads and produces BITWISE the
    jit path's outputs — the warm start changes when compilation happens,
    never what runs."""
    import jax

    from poseidon_tpu.core.net import Net
    from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                       init_train_state, make_mesh)
    from poseidon_tpu.proto.messages import (SolverParameter,
                                             load_net_from_string)
    from poseidon_tpu.runtime.compile_cache import (load_step_executable,
                                                    save_step_executable,
                                                    step_key)

    shapes = {"data": (8, 1, 12, 12), "label": (8,)}
    net = Net(load_net_from_string(_SMALLNET), "TRAIN", source_shapes=shapes)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    mesh = make_mesh()
    # donation off: the test calls BOTH the jit step and the reloaded
    # executable on the same (params, state) trees
    ts = build_train_step(net, sp, mesh, CommConfig(), donate=False)
    params = net.init(jax.random.PRNGKey(0))
    state = init_train_state(params, CommConfig(),
                             int(np.prod(list(mesh.shape.values()))))
    rs = np.random.RandomState(0)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P("data"))
    batch = {"data": jax.device_put(
                 rs.randn(8, 1, 12, 12).astype(np.float32), sh),
             "label": jax.device_put(rs.randint(0, 5, 8), sh)}
    rng = jax.random.PRNGKey(7)

    cache = str(tmp_path / "cc")
    key = step_key(model="elastic_smallnet", backend=jax.default_backend())
    assert load_step_executable(cache, key) is None   # clean miss
    compiled = ts.lowerable.lower(params, state, batch, rng).compile()
    assert save_step_executable(cache, key, compiled) is not None
    loaded = load_step_executable(cache, key)
    assert loaded is not None

    p1, s1, m1 = ts.step(params, state, batch, rng)
    out = loaded(params, state, batch, rng)
    p2, s2, m2 = out[:3]
    np.testing.assert_array_equal(np.asarray(m1["loss"]),
                                  np.asarray(m2["loss"]))
    for l in p1:
        for p in p1[l]:
            np.testing.assert_array_equal(np.asarray(p1[l][p]),
                                          np.asarray(p2[l][p]))


def test_engine_aot_warm_start_loads_across_engines(tmp_path,
                                                    jax_cache_env):
    """Two engine incarnations of the same config against one cache dir:
    the first compiles + serializes, the second LOADS (trace and compile
    skipped) and trains to bit-identical final params."""
    from poseidon_tpu.runtime.compile_cache import (aot_entries,
                                                    enable_compile_cache)

    cache = enable_compile_cache()
    eng1 = _small_engine(tmp_path / "r1", max_iter=3)
    last1 = eng1.train()
    eng1.close()
    assert eng1._aot_exec is not None and not eng1._aot_failed
    assert aot_entries(cache) == 1
    assert eng1.stats.sections["compiled_step"]["source"] == "compiled"

    eng2 = _small_engine(tmp_path / "r2", max_iter=3)
    last2 = eng2.train()
    eng2.close()
    assert eng2._aot_exec is not None
    assert aot_entries(cache) == 1    # loaded, not re-serialized
    assert eng2.stats.sections["compiled_step"]["source"] == "loaded"
    assert last1["loss"] == last2["loss"]


def test_engine_aot_loads_the_stored_step_on_a_new_seed(tmp_path,
                                                        jax_cache_env):
    """Two engines that differ ONLY in ``random_seed``, one cache dir: the
    seed is not in the store's key, so the second LOADS the first's step —
    and the loaded program fed the second seed's weights and ``rng`` is
    the right program: its losses are those of a jit-path engine on the
    second seed, bitwise, through a DROPOUT layer that reads the rng."""
    from poseidon_tpu.runtime.compile_cache import (aot_entries,
                                                    enable_compile_cache)

    def losses(eng, jit_path=False):
        if jit_path:
            eng._aot_enabled = False
        first = eng.train(max_iter=1)["loss"]
        last = eng.train()["loss"]
        eng.close()
        return first, last

    cache = enable_compile_cache()
    eng1 = _small_engine(tmp_path / "r1", _DROPNET, max_iter=3)
    on_3 = losses(eng1)
    assert eng1.stats.sections["compiled_step"]["source"] == "compiled"
    assert eng1.stats.sections["compiled_step"]["stored"] == "yes"

    eng2 = _small_engine(tmp_path / "r2", _DROPNET, {"random_seed": 4},
                         max_iter=3)
    on_4 = losses(eng2)
    assert eng2.stats.sections["compiled_step"]["source"] == "loaded"
    assert eng2._aot_exec is not None and not eng2._aot_failed
    assert aot_entries(cache) == 1

    eng3 = _small_engine(tmp_path / "r3", _DROPNET, {"random_seed": 4},
                         max_iter=3)
    on_4_jit = losses(eng3, jit_path=True)
    assert eng3._aot_exec is None
    assert "compiled_step" not in eng3.stats.sections
    assert on_4 == on_4_jit
    assert on_4 != on_3, "the seed does change the run: its weights, its rng"


def _lowered_step_text(eng) -> str:
    """The train step as ``Engine._load_or_compile_step`` lowers it."""
    batch = eng._next_batch(eng.train_pipelines)
    low = eng.train_step.lowerable or eng.train_step.step
    return low.lower(eng.params, eng.state, batch, eng.rng).as_text()


def _token_engine(tmp_path, seed):
    """OLMoE's block at cut widths (attention, a routed MoE layer with both
    auxiliary losses) with a DROPOUT layer behind the embedding, ADAM and a
    clip, through the Engine on one device."""
    import h5py

    from poseidon_tpu.models import zoo
    from poseidon_tpu.parallel.mesh import make_mesh
    from poseidon_tpu.proto.messages import load_solver
    from poseidon_tpu.runtime.engine import Engine
    tmp_path.mkdir(parents=True, exist_ok=True)
    stream = np.random.RandomState(7).randint(0, 128, 33).astype(np.int32)
    with h5py.File(tmp_path / "tokens.h5", "w") as h:
        h["data"] = np.tile(stream[:-1], (4, 1))
        h["label"] = np.tile(stream[1:], (4, 1))
    (tmp_path / "tokens.txt").write_text(str(tmp_path / "tokens.h5") + "\n")
    net = zoo.olmoe(batch=1, source=str(tmp_path / "tokens.txt"),
                    n_layers=1, hidden=64, heads=4, experts=8, top_k=2,
                    expert_width=32, vocab=128)
    at = [l.name for l in net.layers].index("embed") + 1
    net.layers.insert(at, zoo.dropout("embed_drop", "x0", 0.1))
    (tmp_path / "net.prototxt").write_text(zoo.to_prototxt(net))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path / "net.prototxt"}"\nsolver_type: ADAM\n'
        f'base_lr: 0.004\nlr_policy: "cosine"\ngamma: 0.1\nstepsize: 2\n'
        f'momentum: 0.9\nmomentum2: 0.95\nweight_decay: 0.1\n'
        f'clip_gradients: 1.0\nmax_iter: 8\ndisplay: 1\nsnapshot: 0\n'
        f'snapshot_after_train: false\nsnapshot_prefix: "snap/x"\n'
        f'random_seed: {seed}\n')
    return Engine(load_solver(str(tmp_path / "solver.prototxt")),
                  output_dir=str(tmp_path), mesh=make_mesh(1))


@pytest.mark.parametrize("model", ["cnn_with_dropout", "token_moe"])
def test_lowered_step_text_is_the_same_under_two_seeds(model, tmp_path):
    """Why the seed may leave the key: nothing the step is traced from
    reads it. The weights are made from it outside the step and ``rng``
    is an argument, so two engines on two seeds lower to the same text,
    byte for byte — with a DROPOUT layer (the rng's reader) in both nets,
    and routing, both router losses, ADAM and a clip in the token one. A
    model whose traced program did read the seed would fail here, and
    would need the seed back in ``Engine._aot_step_key``."""
    texts = []
    for seed in (3, 4):
        eng = (_token_engine(tmp_path / str(seed), seed)
               if model == "token_moe" else _small_engine(
                   tmp_path / str(seed), _DROPNET, {"random_seed": seed}))
        texts.append(_lowered_step_text(eng))
        eng.close()
    if model == "token_moe":
        assert "top_k" in texts[0] or "sort" in texts[0]    # it routes
    assert "rng_bit_generator" in texts[0] or "threefry" in texts[0] \
        or "random" in texts[0], "no random draw in the step: a weak case"
    assert texts[0] == texts[1]


def _step_key_of(eng) -> str:
    try:
        return eng._aot_step_key(eng._next_batch(eng.train_pipelines))
    finally:
        eng.close()


_KEY_CASES = {
    # name: (what differs from _small_engine(net=_DROPNET), same key?)
    "random_seed": ({"solver": {"random_seed": 4}}, True),
    "display": ({"solver": {"display": 2}}, True),
    "max_iter_under_a_fixed_rate": ({"solver": {"max_iter": 9}}, True),
    "base_lr": ({"solver": {"base_lr": 0.02}}, False),
    "clip_gradients": ({"solver": {"clip_gradients": 1.0}}, False),
    "batch_shape": ({"net": _DROPNET.replace("batch_size: 8",
                                             "batch_size: 16")}, False),
    "remat_unit": ({"remat": "ip1"}, False),
    "a_layer_s_definition": ({"net": _DROPNET.replace("0.25", "0.5")},
                             False),
    # what a unit keeps follows from the program, the device and the
    # budget the user gave (Engine._compile_step): the budget is in the key
    # and the decision is not, so no start needs a compile to know its key
    "two_budgets": ({"remat": "ip1", "hbm_budget_gb": 2.0,
                     "base": {"remat": "ip1", "hbm_budget_gb": 1.0}}, False),
    "no_budget_on_two_seeds": ({"remat": "ip1",
                                "solver": {"random_seed": 4},
                                "base": {"remat": "ip1"}}, True),
}


@pytest.mark.parametrize("case", sorted(_KEY_CASES))
def test_aot_step_key_follows_the_program_and_nothing_else(case, tmp_path):
    """The store's key is equal where the traced program is (another seed,
    another display cadence, a longer run at a fixed rate) and differs
    where the program does (a rate, a clip, a batch shape, a remat unit, a
    layer's own numbers under the same name and shapes, the memory budget
    a checkpointed step is held to): a stale load is worse than a slow
    start."""
    differs, same = _KEY_CASES[case]
    differs = dict(differs)
    base = differs.pop("base", {})       # what both sides have
    changed = _small_engine(tmp_path / "case", **{"net": _DROPNET, **differs})
    if "remat" in differs:
        assert changed.remat_plan is not None and changed.remat_plan.units
    base = _small_engine(tmp_path / "base", **{"net": _DROPNET, **base})
    assert (_step_key_of(base) == _step_key_of(changed)) is same


@pytest.mark.parametrize("policy, same", [
    ("fixed", True), ("step", True), ("exp", True), ("inv", True),
    ("sigmoid", True), ("multistep", True), ("poly", False),
    ("cosine", False)])
def test_max_iter_is_in_the_key_exactly_where_the_rate_reads_it(
        policy, same, tmp_path):
    """``max_iter`` reaches the traced program under ``poly`` and
    ``cosine`` (the schedule's horizon is a constant of the step) and
    under no other policy: the lowered text says so, and the key follows
    the text — resume-and-train-longer loads the stored step at a fixed or
    stepped rate and misses, rightly, where a longer run is another
    schedule."""
    fields = {"lr_policy": policy, "gamma": 0.5, "power": 0.5,
              "stepsize": 2, "stepvalue": [2, 3]}
    keys, texts = [], []
    for max_iter in (4, 9):
        eng = _small_engine(tmp_path / f"{max_iter}",
                            solver={**fields, "max_iter": max_iter})
        texts.append(_lowered_step_text(eng))
        keys.append(_step_key_of(eng))
    assert (texts[0] == texts[1]) is same
    assert (keys[0] == keys[1]) is same


def test_engine_aot_does_not_reserialize_an_xla_cache_hit(tmp_path,
                                                          jax_cache_env):
    """XLA cache warm, aot/ empty (any edit to the package leaves a cache
    in this state): the step the XLA cache hands back runs, is reported as
    such, and is NOT serialized — XLA:CPU re-serializes it into an entry
    that loads and then dies at its first dispatch."""
    import shutil

    from poseidon_tpu.runtime.compile_cache import (aot_entries,
                                                    enable_compile_cache)

    cache = enable_compile_cache()
    eng1 = _small_engine(tmp_path / "r1", max_iter=3)
    last1 = eng1.train()
    eng1.close()
    assert eng1.stats.sections["compiled_step"]["stored"] == "yes"
    shutil.rmtree(os.path.join(cache, "aot"))

    for run in ("r2", "r3"):
        eng = _small_engine(tmp_path / run, max_iter=3)
        last = eng.train()
        eng.close()
        step = eng.stats.sections["compiled_step"]
        assert step["source"] == "xla_cache" and "error" not in step
        assert step["stored"].startswith("no") and aot_entries(cache) == 0
        assert eng._aot_exec is not None and not eng._aot_failed
        assert last["loss"] == last1["loss"]


def test_engine_survives_an_unwritable_aot_store(tmp_path, jax_cache_env):
    """The store is best-effort: where aot/ cannot be written the compiled
    step still runs and still reports what it holds; only `stored` says
    no (chip_smoke.py reads these fields — a KeyError there refused PR 21
    on the driver's machine)."""
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    with open(os.path.join(cache, "aot"), "w") as f:
        f.write("a file where the store's directory should be")
    eng = _small_engine(tmp_path / "r1", max_iter=3)
    last = eng.train()
    eng.close()
    step = eng.stats.sections["compiled_step"]
    assert step["source"] == "compiled" and step["stored"] == "no (see log)"
    assert "error" not in step and step["pallas_custom_calls"] == 0
    assert eng._aot_exec is not None and np.isfinite(last["loss"])
