"""Persistent columnar world state + vectorized simulation event loop.

Parity contract (ISSUE 5): ``GridSimulation(vector_world=True)`` — the
epoch-batched fused loop over ``core/world.py``'s ``HostArrays`` — must be
bit-identical to the scalar per-event oracle (``vector_world=False``):
same SimMetrics, same job/instance states, same granted credit, with and
without event-time quantization. Plus the satellite regressions: clamped
accrual (busy <= capacity, exact flops accounting), churn purging every
per-host trace, and RNG-stream identity for the prefetched draw batches.
"""
import math
import random

import numpy as np
import pytest

from repro.core import (
    App,
    AppVersion,
    Client,
    ExpDrawCache,
    GridSimulation,
    HostArrays,
    Job,
    Platform,
    ProjectServer,
    default_cpu_plan_class,
    fuzzy_comparator,
    make_population,
    next_id,
    reset_ids,
)
from repro.core.client import ClientJob, ClientPrefs, ClientResource, ProjectAttachment, RunState
from repro.core.types import ResourceType
from repro.core.world import COMPLETION_TOL

DAY = 86400.0


def build_sim(vector_world, epoch=0.0, n_hosts=10, n_jobs=50, horizon=DAY,
              sim_seed=3, pop_seed=1, est_hours=0.15, **pop_kw):
    reset_ids()
    server = ProjectServer(name="p", purge_delay=1e18)
    app = App(name="w", min_quorum=2, init_ninstances=2, delay_bound=4 * 3600.0,
              comparator=fuzzy_comparator(rtol=1e-6, atol=1e-9))
    for osn in ("windows", "mac", "linux"):
        app.add_version(AppVersion(id=next_id("appver"), app_name="w",
                                   platform=Platform(osn, "x86_64"), version_num=1,
                                   plan_class=default_cpu_plan_class()))
    server.add_app(app)
    pop = make_population(n_hosts, seed=pop_seed, horizon=horizon, **pop_kw)
    sim = GridSimulation(server, pop, seed=sim_seed,
                         vector_world=vector_world, epoch=epoch)
    for _ in range(n_jobs):
        server.submit_job(Job(id=next_id("job"), app_name="w",
                              est_flop_count=est_hours * 3600 * 16.5e9), 0.0)
    return server, sim


def run_sim(vector_world, epoch=0.0, horizon=DAY, **kw):
    server, sim = build_sim(vector_world, epoch=epoch, horizon=horizon, **kw)
    m = sim.run(horizon)
    sim.audit_validation()
    states = {
        i: (x.validate_state, x.granted_credit, x.outcome, x.runtime)
        for i, x in server.store.instances.items()
    }
    jobs = {j: x.state for j, x in server.store.jobs.items()}
    return (
        vars(m).copy(), server.counts(), server.credit.total, states, jobs,
        dict(sim._wrong_outputs), server, sim,
    )


CONFIGS = [
    dict(),
    dict(availability=0.6),
    dict(churn_rate=1.0 / (1.2 * DAY)),
    dict(availability=0.55, churn_rate=1.0 / (2 * DAY), error_prob=0.02),
]


class TestVectorWorldParity:
    @pytest.mark.parametrize("epoch", [0.0, 60.0])
    @pytest.mark.parametrize("cfg", range(len(CONFIGS)))
    def test_bit_identical_to_scalar_oracle(self, cfg, epoch):
        """Whole-sim identity: metrics, server counts, credit, instance
        validate-states/credit/outcomes/runtimes, job states, and the
        wrong-output map — continuous and epoch-quantized event times."""
        kw = CONFIGS[cfg]
        a = run_sim(False, epoch=epoch, **kw)
        b = run_sim(True, epoch=epoch, **kw)
        for x, y, name in zip(a[:6], b[:6], (
                "metrics", "counts", "credit", "instance states",
                "job states", "wrong outputs")):
            assert x == y, f"vector world diverged from oracle: {name}"

    def test_rng_stream_identity(self):
        """Same seeds => the vectorized loop's prefetched exponential
        availability draws and the per-event corruption/runtime draws
        reproduce the scalar ``random.Random`` sequences host-for-host: the
        final RNG state and every stochastic outcome coincide."""
        kw = dict(availability=0.5, error_prob=0.05)
        a = run_sim(False, epoch=45.0, **kw)
        b = run_sim(True, epoch=45.0, **kw)
        assert a[5] == b[5]  # per-instance corruption outcomes
        assert a[0] == b[0]
        # identical RNG consumption: the generators end in the same state
        assert a[7].rng.getstate() == b[7].rng.getstate()
        assert len(b[7].world.draws) == 0  # prefetched batches fully drained

    def test_exp_draw_cache_matches_expovariate(self):
        """ExpDrawCache.draw == random.Random.expovariate, bitwise, for any
        prefetch batching."""
        means = [60.0, 3600.0, 8 * 3600.0, 1.5]
        ref = random.Random(42)
        want = [ref.expovariate(1.0 / m) for m in means * 50]
        rng = random.Random(42)
        cache = ExpDrawCache()
        got = []
        i = 0
        for chunk in (1, 7, 32, 160):  # arbitrary prefetch sizes
            cache.prefetch(rng, chunk)
            for _ in range(chunk):
                got.append(cache.draw(rng, 1.0 / means[i % len(means)]))
                i += 1
        assert got == want[: len(got)]


class TestClampedAccrual:
    def test_advance_clamps_at_actual_total(self):
        """Unit-level: advancing past the nominal finish charges at most
        the remaining work — accrued, busy and fraction all cap."""
        world = HostArrays()
        client = Client(
            host_id=1,
            resources={ResourceType.CPU: ClientResource(ResourceType.CPU, 4, 1e10)},
            prefs=ClientPrefs(),
        )
        client.attach(ProjectAttachment(name="p"))
        world.add_host(1, client, 4)
        cj = ClientJob(
            instance_id=7, job_id=7, project="p", app_name="w",
            usage={ResourceType.CPU: 1.0}, est_flops=1e10,
            est_flop_count=1e13, deadline=1e9, state=RunState.RUNNING,
        )
        client.jobs.append(cj)
        world.add_job(1, cj, actual_total=100.0)
        world.sync_run_state(1)
        world.advance_host(1, 70.0)
        assert world.get_accrued(1, 7) == 70.0
        assert world.busy_total() == 70.0
        # event lands 50s after the nominal finish: only 30s left to charge
        world.advance_host(1, 150.0)
        assert world.get_accrued(1, 7) == 100.0
        assert world.busy_total() == 100.0
        assert cj.fraction_done == 1.0
        assert cj.runtime == 100.0
        # REC was debited for executed work only
        assert client.rec.accounts["p"].total_used == 100.0
        # further advances charge nothing
        world.advance_host(1, 500.0)
        assert world.get_accrued(1, 7) == 100.0
        assert world.busy_total() == 100.0

    @pytest.mark.parametrize("vector_world", [False, True])
    def test_busy_bounded_by_capacity_under_epoch(self, vector_world):
        """End-to-end: epoch quantization guarantees events land after
        nominal finish times (completions round up to the grid); clamped
        accrual keeps busy <= capacity and flops accounting exact."""
        a = run_sim(vector_world, epoch=120.0, availability=0.6,
                    n_hosts=8, n_jobs=40, horizon=1.5 * DAY)
        m, server, sim = a[0], a[6], a[7]
        assert m["busy_cpu_seconds"] <= m["capacity_cpu_seconds"]
        # exact flops accounting: every executed instance contributes its
        # est_flop_count exactly once
        per_job = 0.15 * 3600 * 16.5e9
        assert m["flops_done"] == pytest.approx(
            m["instances_executed"] * per_job, rel=0, abs=1e-3
        )
        # and no instance is charged past its drawn actual_total: total
        # busy CPU-seconds is bounded by the sum of actual runtimes over
        # every instance ever dispatched (pre-clamp, availability toggles
        # landing after nominal finish times inflated accrual past this)
        assert m["busy_cpu_seconds"] <= sim._dispatched_actual_total + 1e-6

    def test_completion_within_tolerance_fires_this_epoch(self):
        """A running job the completion predicate already counts as done
        (within ``COMPLETION_TOL`` of its total) gets its completion event
        at the current time: a last-bit difference in accrued runtime, as
        the TPU's non-IEEE f64 leaves, must not move it to the next epoch."""
        _, sim = build_sim(True, epoch=60.0)
        sim.run(3600.0)
        world = sim.world
        host = next(h for h in world.index if len(world.running_rows(h)))
        i, row = world.index[host], world.running_rows(host)[-1]
        for gap, due in ((COMPLETION_TOL / 2, 3600.0), (2 * COMPLETION_TOL, 3660.0)):
            world.q_runtime[row, i] = world.q_total[row, i] - gap
            sim._reschedule_completions(host, 3600.0)
            last = next(e for e in sim._heap if e[1] == sim._seq)
            assert last[0] == due, (gap, last)


class TestChurnPurge:
    @pytest.mark.parametrize("vector_world", [False, True])
    def test_departed_hosts_leave_no_trace(self, vector_world):
        m, counts, credit, states, jobs, wrong, server, sim = run_sim(
            vector_world, churn_rate=1.0 / (0.5 * DAY), horizon=2 * DAY,
            n_hosts=14, n_jobs=40,
        )
        world = sim.world
        departed = [h for h in world.index if h not in sim.specs]
        assert departed, "churn scenario produced no departures"
        for h in departed:
            i = world.index[h]
            assert not world.alive[i]
            assert not world.available[i]
            assert world.q_count[i] == 0
            assert world.queue_jobs[i] == []
            assert world.row_of[i] == {}
            assert world.clients[i] is None
            assert not world.q_running[:, i].any()
            assert h not in sim.clients
            assert h not in sim.running
        # undelivered instance metadata for departed hosts was purged: any
        # instance still marked in-progress on a departed host (the server
        # only learns of the departure via deadline timeouts) must have had
        # its client-side metadata dropped at churn time
        from repro.core import InstanceState

        departed_set = set(departed)
        stranded = [
            i.id
            for i in server.store.instances.values()
            if i.state == InstanceState.IN_PROGRESS
            and i.host_id in departed_set
        ]
        for iid in stranded:
            assert iid not in sim._instance_meta
        # live hosts' running instances keep theirs
        for h in sim.specs:
            for iid in sim.running[h]:
                assert iid in sim._instance_meta
        # server-side traces are purged too: DB row, estimator stats.
        # (Reputation rows are zeroed at churn but may legitimately re-earn
        # entries from results validated after the departure; the immediate
        # zeroing is unit-tested below.)
        for h in departed:
            assert h not in server.store.hosts
            assert h not in server.estimator._host_versions
            assert not any(
                hk == h for hk, _ in server.estimator.host_version
            )

    def test_server_remove_host_clears_reputation_and_stats(self):
        server, sim = build_sim(True, n_hosts=3, n_jobs=6, horizon=DAY)
        hid = next(iter(sim.specs))
        ver = server.store.apps["w"].versions[0]
        server.adaptive.on_validated(hid, ver.id)
        assert server.adaptive.reputation(hid, ver.id) == 1
        host = server.store.hosts[hid]
        job = next(iter(server.store.jobs.values()))
        server.estimator.record(host, ver, job, 100.0)
        assert (hid, ver.id) in server.estimator.host_version
        server.remove_host(hid)
        assert server.adaptive.reputation(hid, ver.id) == 0
        assert (hid, ver.id) not in server.estimator.host_version
        assert hid not in server.store.hosts


class TestWorldInvariants:
    def test_check_invariants_after_run(self):
        for vw in (False, True):
            *_, server, sim = run_sim(vw, availability=0.7, n_hosts=6,
                                      n_jobs=30, horizon=DAY)
            sim.world.check_invariants(strict_dynamic=not vw)

    def test_dirty_host_refresh(self):
        """mark_dirty => columns rebuilt from objects on next snapshot."""
        server, sim = build_sim(True, n_hosts=4, n_jobs=20, horizon=DAY)
        sim.run(1200.0)
        world = sim.world
        hid = next(h for h in sim.specs if world.q_count[world.index[h]] > 0)
        i = world.index[hid]
        j = world.queue_jobs[i][0]
        j.est_wss = 12345.0  # out-of-band object mutation
        world.mark_dirty(hid)
        sim.client_engine.needs_work_world(world, [hid], sim.now)
        assert world.q_wss[0, i] == 12345.0
        assert hid not in world.dirty
        world.check_invariants()
