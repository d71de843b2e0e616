"""Multi-replica serving fleet: prefix-affinity routing, replica-loss
re-dispatch, graceful drain — plus the HeartbeatMonitor clock-domain and
malformed-topic fixes and `serve_continuous`'s preemption drain these
fleet semantics ride on.
"""

import numpy as np
import pytest

from repro.core.strategies.resilience import (
    ALL_JOIN_POINTS,
    FLEET_JOIN_POINTS,
    JOIN_POINTS,
    FaultInjector,
    FaultSpec,
    FleetResilienceAspect,
)
from repro.distributed.fault import HeartbeatMonitor, PreemptionHandler
from repro.monitor.examon import ExamonBroker
from repro.runtime.fleet import ServingFleet, _PollPreemption


def _server(arch="yi-6b", *, extra_aspects=None, **cfg_kw):
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.runtime.server import Server, ServerConfig

    program = Program.from_arch(arch, kind="serve", reduced=True)
    woven = default_weave(program, SHAPES["prefill_32k"], {},
                          extra_aspects=extra_aspects or [])
    cfg_kw.setdefault("max_cache_len", 24)
    cfg_kw.setdefault("decode_tokens", 4)
    return Server(woven, ServerConfig(**cfg_kw))


def _fleet_prompts(n=8, shared=8, tail=3, seed=0):
    """A shared-system-prompt workload: every prompt opens with the same
    `shared` tokens (page-aligned at page_size=8), distinct tails."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, 90, shared)
    return [np.concatenate([sys_prompt, rng.integers(1, 90, tail)])
            .astype(np.int64) for _ in range(n)]


@pytest.fixture(scope="module")
def woven():
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave

    program = Program.from_arch("yi-6b", kind="serve", reduced=True)
    return default_weave(program, SHAPES["prefill_32k"], {})


@pytest.fixture(scope="module")
def factory(woven):
    from repro.runtime.server import Server, ServerConfig

    return lambda: Server(woven, ServerConfig(
        max_cache_len=24, decode_tokens=4, max_batch=2, page_size=8))


@pytest.fixture(scope="module")
def baseline(factory):
    """Single-server fault-free serve of the shared workload — the
    bit-parity reference every fleet scenario is held to."""
    prompts = _fleet_prompts()
    return prompts, factory().serve_continuous(prompts, decode_tokens=4)


def _parity(outs, base):
    return all(np.array_equal(a, b) for a, b in zip(outs, base))


# ---------------------------------------------------------------------------
# HeartbeatMonitor: clock domains, malformed beats, liveness (satellites)
# ---------------------------------------------------------------------------


class TestHeartbeatMonitor:
    def test_liveness_declared_on_monitor_clock(self):
        """Beats are arrival-stamped with the monitor's own clock, so a
        publisher stamping its beats in a *different* clock domain (epoch
        seconds here, vs the monitor's logical counter) cannot corrupt
        liveness."""
        broker = ExamonBroker()
        tick = {"now": 0.0}
        dead = []
        mon = HeartbeatMonitor(broker, dead_after_s=2.0,
                               clock=lambda: tick["now"],
                               on_dead=dead.append)
        broker.publish("fleet/heartbeat/@host0", 0.01,
                       timestamp=1.7e9)  # epoch-domain publisher ts
        broker.publish("fleet/heartbeat/@host1", 0.01, timestamp=-5.0)
        tick["now"] = 1.0
        broker.publish("fleet/heartbeat/@host1", 0.01, timestamp=0.0)
        tick["now"] = 3.0
        mon.check_liveness()
        assert dead == [0]          # host1 beat at 1.0: 2.0 elapsed, alive
        assert mon.dead == {0}
        tick["now"] = 4.0
        mon.check_liveness()
        assert set(dead) == {0, 1}  # host1 now 3.0 silent

    def test_liveness_default_clock_is_monotonic_both_sides(self):
        """With no custom clock, publish-side default and check side are
        both time.monotonic — a fresh beat is never declared dead."""
        broker = ExamonBroker()
        dead = []
        mon = HeartbeatMonitor(broker, dead_after_s=30.0,
                               on_dead=dead.append)
        broker.publish("fleet/heartbeat/@host0", 0.01)
        mon.check_liveness()
        assert dead == [] and not mon.dead

    def test_dead_host_revives_on_new_beat(self):
        broker = ExamonBroker()
        tick = {"now": 0.0}
        mon = HeartbeatMonitor(broker, dead_after_s=1.0,
                               clock=lambda: tick["now"])
        broker.publish("fleet/heartbeat/@host3", 0.01)
        tick["now"] = 5.0
        mon.check_liveness()
        assert mon.dead == {3}
        broker.publish("fleet/heartbeat/@host3", 0.01)  # spare took the slot
        assert mon.dead == set()
        mon.check_liveness()
        assert mon.dead == set()

    def test_malformed_topics_dropped_and_counted(self):
        broker = ExamonBroker()
        mon = HeartbeatMonitor(broker)
        # none of these may raise inside the broker callback
        broker.publish("fleet/heartbeat/oops", 0.01)
        broker.publish("fleet/heartbeat/@hostX", 0.01)
        broker.publish("fleet/heartbeat/@host", 0.01)
        broker.publish("fleet/heartbeat/@host7", 0.01)  # well-formed
        assert mon.malformed_beats == 3
        assert 7 in mon._last_seen

    def test_forget_clears_all_host_state(self):
        broker = ExamonBroker()
        tick = {"now": 0.0}
        mon = HeartbeatMonitor(broker, dead_after_s=1.0,
                               clock=lambda: tick["now"])
        broker.publish("fleet/heartbeat/@host2", 0.01)
        tick["now"] = 5.0
        mon.check_liveness()
        assert mon.dead == {2}
        mon.forget(2)
        assert 2 not in mon._last_seen and mon.dead == set()
        mon.check_liveness()   # no stale entry to re-declare
        assert mon.dead == set()


# ---------------------------------------------------------------------------
# serve_continuous graceful drain (PreemptionHandler satellite)
# ---------------------------------------------------------------------------


class TestServeDrain:
    def test_pending_from_start_drains_everything(self):
        srv = _server()
        pre = PreemptionHandler(install=False)
        pre.request()  # SIGTERM before the wave starts
        prompts = _fleet_prompts(3)
        outs = srv.serve_continuous(prompts, preemption=pre)
        assert all(len(o) == 0 for o in outs)
        assert {o["status"] for o in srv.last_outcomes} == {"drained"}
        assert srv.last_fault_stats["drained"] == 3

    def test_midwave_sigterm_finishes_inflight_drains_waiting(self):
        """SIGTERM during an active wave: the admitted cohort finishes
        its full decode (bit-identical to an unpreempted serve), nothing
        new is admitted, the rest returns structured drained outcomes."""
        prompts = _fleet_prompts(5)
        clean_srv = _server(max_batch=2, page_size=8)
        base = clean_srv.serve_continuous(prompts, decode_tokens=4)

        class _SigtermAfterFirstPoll(PreemptionHandler):
            def __init__(self):
                super().__init__(install=False)
                self.polls = 0

            @property
            def pending(self):
                self.polls += 1
                if self.polls > 1:
                    self.request()
                return super().pending

        srv = _server(max_batch=2, page_size=8)
        pre = _SigtermAfterFirstPoll()
        outs = srv.serve_continuous(prompts, decode_tokens=4,
                                    preemption=pre)
        statuses = {o["rid"]: o["status"] for o in srv.last_outcomes}
        finished = [r for r, s in statuses.items() if s == "ok"]
        drained = [r for r, s in statuses.items() if s == "drained"]
        assert len(finished) == 2           # the admitted cohort
        assert len(drained) == 3            # nothing new admitted
        for r in finished:
            assert np.array_equal(outs[r], base[r])
        for r in drained:
            assert len(outs[r]) == 0
        assert srv.last_fault_stats["drained"] == 3

    def test_no_preemption_keeps_bit_parity_and_memo(self):
        prompts = _fleet_prompts(3)
        a = _server().serve_continuous(prompts, decode_tokens=4)
        b = _server().serve_continuous(prompts, decode_tokens=4,
                                       preemption=None)
        assert _parity(a, b)


# ---------------------------------------------------------------------------
# Fleet join points + aspect
# ---------------------------------------------------------------------------


class TestFleetWeave:
    def test_join_point_split(self):
        # the 8-point serving sweep matrix is untouched; fleet points are
        # validation-visible but separate
        assert set(FLEET_JOIN_POINTS) == {"route", "replica_loss", "drain"}
        assert not set(FLEET_JOIN_POINTS) & set(JOIN_POINTS)
        assert set(ALL_JOIN_POINTS) == set(JOIN_POINTS) | set(FLEET_JOIN_POINTS)

    def test_fleet_specs_validate_and_fire(self):
        inj = FaultInjector([FaultSpec("replica_loss", "raise", at=1)])
        assert inj.fire("replica_loss", rid=0) is None
        with pytest.raises(Exception):
            inj.fire("replica_loss", rid=1)
        with pytest.raises(ValueError):
            FaultSpec("not_a_point", "raise")

    def test_aspect_weaves_policy_and_injector(self):
        inj = FaultInjector()
        srv = _server(extra_aspects=[FleetResilienceAspect(
            inj, retries=5, wave_size=2, affinity=False)])
        extra = srv.woven.state.extra
        assert extra["fleet_injector"] is inj
        assert extra["fleet_resilience"]["retries"] == 5
        assert extra["fleet_resilience"]["wave_size"] == 2
        assert extra["fleet_resilience"]["affinity"] is False

    def test_fleet_resolves_woven_policy(self, woven):
        from repro.core.program import Program
        from repro.configs.base import SHAPES
        from repro.launch.weave import default_weave
        from repro.runtime.server import Server, ServerConfig

        inj = FaultInjector()
        program = Program.from_arch("yi-6b", kind="serve", reduced=True)
        w = default_weave(program, SHAPES["prefill_32k"], {},
                          extra_aspects=[FleetResilienceAspect(
                              inj, retries=7, wave_size=2)])
        fleet = ServingFleet(
            lambda: Server(w, ServerConfig(max_cache_len=24,
                                           decode_tokens=4)),
            replicas=1)
        assert fleet.policy["retries"] == 7
        assert fleet.policy["wave_size"] == 2
        assert fleet.injector is inj
        # explicit constructor args still win
        fleet2 = ServingFleet(
            lambda: Server(w, ServerConfig(max_cache_len=24,
                                           decode_tokens=4)),
            replicas=1, retries=1)
        assert fleet2.policy["retries"] == 1


# ---------------------------------------------------------------------------
# ServingFleet end-to-end scenarios
# ---------------------------------------------------------------------------


class TestServingFleet:
    def test_clean_fleet_parity_and_affinity(self, factory, baseline):
        prompts, base = baseline
        fleet = ServingFleet(factory, replicas=2, wave_size=3)
        outs = fleet.serve(prompts, decode_tokens=4)
        st = fleet.last_fleet_stats
        assert st["outcomes"] == {"ok": len(prompts)}
        assert _parity(outs, base)
        # injection off: zero fleet events, routing-only overhead
        assert st["events"] == [] and st["injected_events"] == []
        # shared-system-prompt workload warms the prefix index on >= 2
        # replicas (wave_size spill) and affinity routing actually fires
        assert len(st["replicas_with_prefix_hits"]) >= 2
        assert st["affinity_hits"] > 0

    def test_devices_place_each_replica(self, woven, baseline):
        """`devices` builds replica i on devices[i % n]: its params, and
        the page pool its serve allocates, live there, and the output
        still matches the single-server baseline."""
        import jax

        from repro.runtime.server import Server, ServerConfig

        prompts, base = baseline
        dev = jax.devices()[-1]
        fleet = ServingFleet(
            lambda **kw: Server(woven, ServerConfig(
                max_cache_len=24, decode_tokens=4, max_batch=2,
                page_size=8), **kw),
            replicas=2, spares=1, wave_size=3, devices=[dev])
        for rep in list(fleet.replicas) + list(fleet.spares):
            assert rep.server.device == dev
            assert {d for x in jax.tree.leaves(rep.server.params)
                    for d in x.devices()} == {dev}
        assert _parity(fleet.serve(prompts, decode_tokens=4), base)

    def test_kill_midwave_recovers_with_parity(self, factory, baseline):
        prompts, base = baseline
        inj = FaultInjector.single("replica_loss", "raise", at=1)
        fleet = ServingFleet(factory, replicas=2, spares=1, wave_size=3,
                             injector=inj)
        outs = fleet.serve(prompts, decode_tokens=4)
        st = fleet.last_fleet_stats
        assert st["outcomes"] == {"ok": len(prompts)}   # 100% recovery
        assert _parity(outs, base)                       # bit-parity
        kinds = [e["kind"] for e in st["events"]]
        assert "replica_loss" in kinds and "declared_dead" in kinds
        assert "spare_in" in kinds and st["spares_left"] == 0
        assert st["redispatched"] >= 1
        # the kill wave's completed requests were kept, not replayed
        loss = next(e for e in st["events"] if e["kind"] == "replica_loss")
        assert loss["kept"] >= 1
        red = [o for o in fleet.last_outcomes if o["attempts"] > 0]
        assert red and all(np.array_equal(outs[o["rid"]], base[o["rid"]])
                           for o in red)

    def test_drain_midwave_hands_queue_to_peers(self, factory, baseline):
        prompts, base = baseline
        fleet = ServingFleet(factory, replicas=2, spares=1, wave_size=4)
        fleet.request_drain(0)
        outs = fleet.serve(prompts, decode_tokens=4)
        st = fleet.last_fleet_stats
        assert st["outcomes"] == {"ok": len(prompts)}
        assert _parity(outs, base)
        drain = next(e for e in st["events"] if e["kind"] == "drain")
        assert drain["host"] == 0
        assert drain["finished"] >= 1       # in-flight cohort completed
        assert drain["handoff"] >= 1        # waiting queue went to peers
        assert not any(r.host == 0 and r.alive for r in fleet.replicas)
        assert "spare_in" in [e["kind"] for e in st["events"]]

    def test_injected_drain_join_point(self, factory, baseline):
        prompts, base = baseline
        inj = FaultInjector.single("drain", "raise", at=0)
        fleet = ServingFleet(factory, replicas=2, wave_size=4,
                             injector=inj)
        outs = fleet.serve(prompts, decode_tokens=4)
        st = fleet.last_fleet_stats
        assert st["outcomes"] == {"ok": len(prompts)}
        assert _parity(outs, base)
        assert any(e["kind"] == "drain" for e in st["events"])
        assert any(e["point"] == "drain" for e in st["injected_events"])

    def test_route_fault_degrades_to_least_loaded(self, factory, baseline):
        prompts, base = baseline
        inj = FaultInjector([FaultSpec("route", "raise", at=0, repeat=3)])
        fleet = ServingFleet(factory, replicas=2, wave_size=3,
                             injector=inj)
        outs = fleet.serve(prompts, decode_tokens=4)
        st = fleet.last_fleet_stats
        # a routing fault never loses the request
        assert st["outcomes"] == {"ok": len(prompts)}
        assert _parity(outs, base)
        assert sum(1 for e in st["injected_events"]
                   if e["point"] == "route") == 3

    def test_fleet_deadline_retires_with_partial(self, factory):
        prompts = _fleet_prompts()
        inj = FaultInjector.single("replica_loss", "raise", at=1)
        fleet = ServingFleet(factory, replicas=2, wave_size=3,
                             injector=inj, deadline_s=0.0)
        outs = fleet.serve(prompts, decode_tokens=4)
        st = fleet.last_fleet_stats
        assert st["outcomes"].get("deadline_exceeded", 0) >= 1
        assert st["outcomes"].get("ok", 0) >= 1   # completed work kept
        overdue = [o for o in fleet.last_outcomes
                   if o["status"] == "deadline_exceeded"]
        # partial output rides out with the structured outcome
        assert all(o["tokens"] == len(outs[o["rid"]]) for o in overdue)

    def test_retry_budget_exhaustion_fails_structurally(self, factory):
        # every dispatch kills the serving replica; one replica, no
        # spares: the victim request exhausts its re-dispatch budget and
        # fails *structurally*, the fleet never raises
        inj = FaultInjector([FaultSpec("replica_loss", "raise",
                                       at=0, repeat=64)])
        fleet = ServingFleet(factory, replicas=1, wave_size=2,
                             injector=inj, retries=1, kill_step=0)
        prompts = _fleet_prompts(2)
        fleet.serve(prompts, decode_tokens=4)
        st = fleet.last_fleet_stats
        assert st["outcomes"].get("failed", 0) >= 1

    def test_affinity_off_still_serves_with_parity(self, factory, baseline):
        prompts, base = baseline
        fleet = ServingFleet(factory, replicas=2, wave_size=3,
                             affinity=False)
        outs = fleet.serve(prompts, decode_tokens=4)
        assert fleet.last_fleet_stats["affinity_hits"] == 0
        assert _parity(outs, base)

    def test_poll_preemption_semantics(self):
        pre = _PollPreemption(after=1)
        assert pre.pending is False
        assert pre.pending is True and pre.pending is True
