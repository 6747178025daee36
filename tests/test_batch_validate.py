"""Batch validation engine (core/batch_validate.py): parity with the
scalar check_set/credit/reputation oracle, digest contracts, fallback
behaviour, and the validation-pending store index."""
import random

import numpy as np
import pytest

from repro.core import (
    AdaptiveReplication,
    App,
    AppVersion,
    CreditSystem,
    GridSimulation,
    Host,
    InstanceOutcome,
    InstanceState,
    Job,
    JobInstance,
    JobState,
    JobStore,
    Platform,
    ProcessingResource,
    ProjectServer,
    ResourceType,
    Transitioner,
    ValidateState,
    bitwise_digest_batch,
    check_set,
    default_cpu_plan_class,
    digest_batch_for,
    fuzzy_comparator,
    make_population,
    next_id,
    reset_ids,
)


# ---------------------------------------------------------------------------
# store-level twin ticks
# ---------------------------------------------------------------------------


def build_pending(
    n_jobs=200,
    per_job=2,
    quorum=2,
    bad_frac=0.1,
    payload="float",
    comparator="fuzzy",
    batch_validate=True,
    adaptive=True,
    seed=3,
    dim=32,
):
    """A store whose jobs all sit at the validation step."""
    reset_ids()
    rng = random.Random(seed)
    rs = np.random.RandomState(seed)
    store = JobStore()
    cmp = {
        "fuzzy": fuzzy_comparator(rtol=1e-6, atol=1e-9),
        "bitwise": None,
        "badfrac": fuzzy_comparator(rtol=1e-6, atol=1e-9, max_bad_fraction=0.5),
        "custom": lambda a, b: abs(a - b) < 0.5,
    }[comparator]
    app = App(
        name="w",
        min_quorum=quorum,
        init_ninstances=quorum,
        max_success_instances=max(6, per_job + 2),
        comparator=cmp,
    )
    vid = next_id("appver")
    app.add_version(
        AppVersion(
            id=vid,
            app_name="w",
            platform=Platform("linux", "x86_64"),
            version_num=1,
            plan_class=default_cpu_plan_class(),
        )
    )
    store.add_app(app)
    for h in range(40):
        store.add_host(
            Host(
                id=h + 1,
                platforms=(Platform("linux", "x86_64"),),
                resources={
                    ResourceType.CPU: ProcessingResource(ResourceType.CPU, 4, 16.5e9)
                },
                volunteer_id=(h % 30) + 1,  # some hosts share a volunteer
            )
        )
    for _ in range(n_jobs):
        job = Job(
            id=next_id("job"),
            app_name="w",
            est_flop_count=0.2 * 3600 * 16.5e9,
            min_quorum=quorum,
            init_ninstances=quorum,
            max_success_instances=max(6, per_job + 2),
        )
        store.submit_job(job)
        if payload == "float":
            truth = float(job.id) * 1.5
        else:
            truth = rs.standard_normal(dim).astype(np.float32)
        for k in range(per_job):
            inst = store.create_instance(job)
            inst.host_id = rng.randrange(40) + 1
            inst.app_version_id = vid
            inst.state = InstanceState.IN_PROGRESS
            inst.state = InstanceState.OVER
            inst.outcome = InstanceOutcome.SUCCESS
            inst.runtime = 700.0 + rng.random() * 100
            inst.peak_flop_count = inst.runtime * 16.5e9
            if rng.random() < bad_frac:
                if payload == "float":
                    inst.output = truth + rng.uniform(1.0, 2.0)
                else:
                    inst.output = truth + rs.uniform(1, 2, size=dim).astype(np.float32)
            else:
                inst.output = truth
    tr = Transitioner(
        store=store,
        credit=CreditSystem(),
        adaptive=AdaptiveReplication() if adaptive else None,
        batch_validate=batch_validate,
    )
    return store, tr


def snapshot(store, tr):
    return {
        "instances": {
            i: (x.validate_state, x.claimed_credit, x.granted_credit, x.outcome)
            for i, x in store.instances.items()
        },
        "jobs": {
            j: (x.state, x.canonical_instance_id, x.transition_flag)
            for j, x in store.jobs.items()
        },
        "metrics": dict(vars(tr.metrics)),
        "credit_total": dict(tr.credit.total),
        "credit_recent": dict(tr.credit.recent),
        "reputation": tr.adaptive.consecutive_valid if tr.adaptive else None,
    }


def run_twins(**kw):
    """Build scalar/engine twins, tick each right after building (the id
    counters are global), and return both snapshots."""
    sa, ta = build_pending(batch_validate=False, **kw)
    ta.tick(60.0)
    snap_a = snapshot(sa, ta)
    sb, tb = build_pending(batch_validate=True, **kw)
    tb.tick(60.0)
    snap_b = snapshot(sb, tb)
    sb.check_invariants()
    sa.check_invariants()
    return snap_a, snap_b, sa, sb


class TestTickParity:
    """One validate-pass tick through the engine must equal the scalar
    oracle on validate states, canonicals, granted credit (bit-exact),
    metrics, and reputation."""

    @pytest.mark.parametrize(
        "kw",
        [
            dict(),
            dict(per_job=3, quorum=3, bad_frac=0.4),  # contested
            dict(per_job=6, quorum=3, bad_frac=0.5),  # malicious-heavy
            dict(comparator="bitwise"),
            dict(payload="array"),
            dict(payload="array", comparator="bitwise"),
            dict(quorum=1, per_job=1, bad_frac=0.0),  # trusted singletons
            dict(adaptive=False),
        ],
        ids=[
            "steady",
            "contested",
            "malicious",
            "bitwise",
            "tensor",
            "tensor-bitwise",
            "singleton",
            "no-adaptive",
        ],
    )
    def test_tick_identical(self, kw):
        snap_a, snap_b, _, _ = run_twins(**kw)
        assert snap_a == snap_b

    def test_multi_tick_convergence(self):
        """Tie-breakers created by tick 1 are validated by later ticks:
        the whole multi-round cascade must stay identical."""

        def run(batch):
            store, tr = build_pending(
                batch_validate=batch, per_job=2, quorum=2, bad_frac=0.3
            )
            for t in range(5):
                tr.tick(60.0 * (t + 1))
                # completed tie-breakers: report them as agreeing successes
                for job in store.jobs.values():
                    truth = float(job.id) * 1.5
                    for inst in store.job_instances(job.id):
                        if inst.state == InstanceState.UNSENT:
                            inst.host_id = (inst.id % 40) + 1
                            inst.app_version_id = next(iter(store.app_versions))
                            inst.state = InstanceState.IN_PROGRESS
                            inst.state = InstanceState.OVER
                            inst.outcome = InstanceOutcome.SUCCESS
                            inst.runtime = 750.0
                            inst.peak_flop_count = inst.runtime * 16.5e9
                            inst.output = truth
                            job.transition_flag = True
            return store, tr

        sa, ta = run(False)
        snap_a = snapshot(sa, ta)
        sb, tb = run(True)
        snap_b = snapshot(sb, tb)
        assert snap_a == snap_b
        assert any(
            j.state == JobState.SUCCESS for j in sb.jobs.values()
        )  # the cascade actually validated work
        sb.check_invariants()

    def test_sharded_transitioners_identical(self):
        def run(batch):
            store, _ = build_pending(batch_validate=batch, bad_frac=0.3)
            credit = CreditSystem()
            adaptive = AdaptiveReplication()
            trs = [
                Transitioner(
                    store=store,
                    credit=credit,
                    adaptive=adaptive,
                    instance=i,
                    n_instances=2,
                    batch_validate=batch,
                )
                for i in range(2)
            ]
            for tr in trs:
                tr.tick(60.0)
            return store, credit, adaptive

        sa, ca, aa = run(False)
        sb, cb, ab = run(True)
        assert {
            i: (x.validate_state, x.granted_credit) for i, x in sa.instances.items()
        } == {i: (x.validate_state, x.granted_credit) for i, x in sb.instances.items()}
        assert ca.total == cb.total
        assert aa.consecutive_valid == ab.consecutive_valid
        sb.check_invariants()

    def test_scalar_fallback_for_undigestable_comparators(self):
        """Comparators without a digest hook (custom fn, fuzzy with a
        bad-fraction allowance) route through scalar check_set — results
        still identical."""
        for comparator in ("custom", "badfrac"):
            snap_a, snap_b, _, sb = run_twins(comparator=comparator, bad_frac=0.3)
            assert snap_a == snap_b, comparator
            app = sb.apps["w"]
            assert digest_batch_for(app.comparator) is None

    def test_straggler_validates_against_canonical(self):
        """A fresh success reported while the job already has a canonical
        instance takes the §4 straggler path in both engines."""

        def run(batch):
            store, tr = build_pending(
                n_jobs=30, batch_validate=batch, bad_frac=0.0
            )
            tr.tick(60.0)
            vid = next(iter(store.app_versions))
            for j, job in enumerate(store.jobs.values()):
                # forge the state the paper describes: job active again with
                # a canonical present and one late fresh success
                inst = store.create_instance(job)
                inst.host_id = (j % 40) + 1
                inst.app_version_id = vid
                inst.state = InstanceState.IN_PROGRESS
                inst.state = InstanceState.OVER
                inst.outcome = InstanceOutcome.SUCCESS
                inst.runtime = 800.0
                inst.peak_flop_count = inst.runtime * 16.5e9
                inst.output = (
                    float(job.id) * 1.5 if j % 3 else float(job.id) * 1.5 + 1.3
                )
                job.state = JobState.ACTIVE
                job.transition_flag = True
            tr.tick(120.0)
            return store, tr

        sa, ta = run(False)
        snap_a = snapshot(sa, ta)
        sb, tb = run(True)
        snap_b = snapshot(sb, tb)
        assert snap_a == snap_b
        states = [i.validate_state for i in sb.instances.values()]
        assert ValidateState.INVALID in states  # disagreeing stragglers seen


class TestKernelGroupingWithinJobs:
    """The JAX engine groups tensor results through the Pallas kernel one
    job at a time: a tick compares each job's results only with each
    other, in the greedy order, and decides what the scalar path does."""

    D = 700  # elements a result: past two 256-lane rows

    def _payloads(self):
        rs = np.random.RandomState(5)
        honest = [rs.standard_normal(self.D).astype(np.float32) for _ in range(7)]

        def corrupt(x, at):
            y = x.copy()
            y[at] += np.float32(1.5)
            return y

        nan = honest[3].copy()
        nan[self.D - 1] = np.nan
        return [
            [honest[0], honest[0]],                                    # honest pair
            [honest[1], honest[1] * np.float32(1 + 1e-7)],            # honest pair
            [corrupt(honest[2], [0, 350]), honest[2], honest[2]],      # C, H, H
            [honest[3], nan, honest[3]],                               # a NaN replica
            [honest[0], honest[0]],                                    # job 0's payload
            [honest[0], honest[5], honest[5]],                         # job 0's, outvoted
            [honest[6], corrupt(honest[6], [self.D - 1]), honest[6]],  # H, C, H
        ]

    def _build(self, outputs, batch_validate):
        store, tr = build_pending(n_jobs=0, batch_validate=batch_validate)
        app = store.apps["w"]
        vid = next(iter(store.app_versions))
        jobs = []
        for j, outs in enumerate(outputs):
            job = Job(id=next_id("job"), app_name="w", est_flop_count=1e12,
                      min_quorum=2, init_ninstances=2,
                      max_success_instances=app.max_success_instances)
            store.submit_job(job)
            for k, out in enumerate(outs):
                inst = store.create_instance(job)
                inst.host_id = 3 * j + k + 1
                inst.app_version_id = vid
                inst.state = InstanceState.IN_PROGRESS
                inst.state = InstanceState.OVER
                inst.outcome = InstanceOutcome.SUCCESS
                inst.runtime = 750.0
                inst.peak_flop_count = inst.runtime * 16.5e9
                inst.output = out
            jobs.append(job)
        if batch_validate:
            tr.engine_backend = "jax"  # the engine is built on the first tick
        return store, tr, jobs

    @staticmethod
    def _greedy_comparisons(outs, same):
        """Comparisons the pinned greedy grouping makes inside one job; a
        NaN-carrying result is compared with nothing."""
        n, reps = 0, []
        for x in outs:
            if np.isnan(x).any():
                continue
            for r in reps:
                n += 1
                if same(x, r):
                    break
            else:
                reps.append(x)
        return n

    def test_one_tick_compares_within_each_job_only(self, monkeypatch):
        pytest.importorskip("jax")
        from repro.kernels.quorum_compare import ops

        calls = {"n": 0}
        real = ops.quorum_compare

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(ops, "quorum_compare", counting)
        outputs = self._payloads()

        sa, ta, jobs_a = self._build(outputs, batch_validate=False)
        ta.tick(60.0)
        snap_a = snapshot(sa, ta)
        sb, tb, jobs_b = self._build(outputs, batch_validate=True)
        tb.tick(60.0)
        snap_b = snapshot(sb, tb)

        same = sb.apps["w"].comparator
        assert calls["n"] == sum(self._greedy_comparisons(o, same) for o in outputs) == 12
        assert snap_a == snap_b
        sb.check_invariants()

        def states(job):
            return [i.validate_state for i in sb.job_instances(job.id)]

        v, x = ValidateState.VALID, ValidateState.INVALID
        assert states(jobs_b[2]) == [x, v, v]
        assert states(jobs_b[3]) == [v, x, v]
        assert states(jobs_b[6]) == [v, x, v]
        # jobs 0 and 4 hold bit-identical payloads, and job 5 holds the same
        # payload beside two others: each job gets verdicts of its own
        assert states(jobs_b[0]) == states(jobs_b[4]) == [v, v]
        assert states(jobs_b[5]) == [x, v, v]
        canon = [sb.jobs[j.id].canonical_instance_id for j in jobs_b]
        assert len(set(canon)) == len(jobs_b)
        assert canon[5] == sb.job_instances(jobs_b[5].id)[1].id


# ---------------------------------------------------------------------------
# whole-simulation twins (the acceptance-criterion parity)
# ---------------------------------------------------------------------------


def make_server(batch_validate, adaptive=False, quorum=2):
    server = ProjectServer(
        name="p", purge_delay=1e18, batch_validate=batch_validate
    )
    app = App(
        name="w",
        min_quorum=quorum,
        init_ninstances=quorum,
        delay_bound=4 * 3600.0,
        adaptive_replication=adaptive,
        comparator=fuzzy_comparator(rtol=1e-6, atol=1e-9),
    )
    for osn in ("windows", "mac", "linux"):
        app.add_version(
            AppVersion(
                id=next_id("appver"),
                app_name="w",
                platform=Platform(osn, "x86_64"),
                version_num=1,
                plan_class=default_cpu_plan_class(),
            )
        )
    server.add_app(app)
    return server


def run_sim(batch_validate, n_jobs=50, n_hosts=12, horizon=2 * 86400.0, **kw):
    reset_ids()
    server = make_server(batch_validate, adaptive=kw.pop("adaptive", False))
    for _ in range(n_jobs):
        server.submit_job(
            Job(id=next_id("job"), app_name="w", est_flop_count=0.2 * 3600 * 16.5e9)
        )
    pop = make_population(n_hosts, seed=1, **kw)
    sim = GridSimulation(server, pop, seed=3)
    m = sim.run(horizon)
    sim.audit_validation()
    return server, sim, m


class TestSimulationParity:
    """Whole-simulation engine-vs-oracle identity: metrics, job validate
    states, and granted credit (the PR acceptance criterion)."""

    @pytest.mark.parametrize(
        "kw",
        [
            dict(),
            dict(error_prob=0.05, malicious_fraction=0.2),
            dict(adaptive=True, error_prob=0.02, malicious_fraction=0.05,
                 horizon=3 * 86400.0),
            dict(availability=0.6, horizon=3 * 86400.0),
        ],
        ids=["clean", "faulty", "adaptive", "intermittent"],
    )
    def test_sim_identical(self, kw):
        srv_b, sim_b, m_b = run_sim(True, **dict(kw))
        srv_s, sim_s, m_s = run_sim(False, **dict(kw))
        assert vars(m_b) == vars(m_s)
        assert {
            i: (x.validate_state, x.claimed_credit, x.granted_credit)
            for i, x in srv_b.store.instances.items()
        } == {
            i: (x.validate_state, x.claimed_credit, x.granted_credit)
            for i, x in srv_s.store.instances.items()
        }
        assert {j: (x.state, x.canonical_instance_id) for j, x in srv_b.store.jobs.items()} == \
               {j: (x.state, x.canonical_instance_id) for j, x in srv_s.store.jobs.items()}
        assert srv_b.credit.total == srv_s.credit.total
        assert srv_b.adaptive.consecutive_valid == srv_s.adaptive.consecutive_valid
        for tb, ts in zip(srv_b.transitioners, srv_s.transitioners):
            assert vars(tb.metrics) == vars(ts.metrics)
        assert m_b.completed_instances > 0  # the scenario did real work


# ---------------------------------------------------------------------------
# digest contracts
# ---------------------------------------------------------------------------


class TestDigests:
    def test_bitwise_float_semantics(self):
        d = bitwise_digest_batch([1.5, 1.5, 2.0, -0.0, 0.0, float("nan"), float("nan")])
        assert d[0] == d[1] != d[2]
        assert d[3] == d[4]  # -0.0 == 0.0 under Python ==
        assert d[5] != d[6]  # NaN equals nothing, itself included

    def test_bitwise_numeric_cross_type(self):
        d = bitwise_digest_batch([1, 1.0, True, 2])
        assert d[0] == d[1] == d[2] != d[3]  # 1 == 1.0 == True

    def test_bitwise_ndarray_one_ulp(self):
        a = np.arange(8, dtype=np.float32)
        b = a.copy()
        b[3] = np.nextafter(b[3], np.float32(10))
        d = bitwise_digest_batch([{"x": a}, {"x": a.copy()}, {"x": b}])
        assert d[0] == d[1] != d[2]

    def test_mix_vector_is_hash_derived_odd_and_deterministic(self):
        """The row-hash multipliers are blake2b-derived constants: odd (so
        each is invertible mod 2^64), stable across calls/processes, and
        built without touching any RNG namespace (rng-discipline)."""
        from repro.core.validator import _mix_cache, _mix_vector

        _mix_cache.pop(7, None)
        a = _mix_vector(7)
        b = _mix_vector(7)
        assert a is b  # cached
        assert a.dtype == np.int64 and a.shape == (7,)
        assert np.all(a % 2 != 0)
        _mix_cache.pop(7, None)
        c = _mix_vector(7)
        assert np.array_equal(a, c)  # re-derivation is bit-identical
        assert len(set(a.tolist())) == 7  # no degenerate repeats

    def test_bitwise_matches_comparator_on_random_payloads(self):
        from repro.core.validator import bitwise_equal

        rng = np.random.RandomState(0)
        outs = [rng.randint(0, 3, size=6).astype(np.float64) for _ in range(40)]
        d = bitwise_digest_batch(outs)
        for i in range(len(outs)):
            for j in range(len(outs)):
                assert (d[i] == d[j]) == bitwise_equal(outs[i], outs[j])

    @pytest.mark.parametrize("rtol,atol", [(1e-6, 1e-9), (0.0, 0.5), (1e-4, 0.0)])
    def test_fuzzy_buckets_follow_comparator(self, rtol, atol):
        """Well-separated-or-identical payloads: digest grouping must agree
        with the pairwise comparator (the documented bucketing contract)."""
        cmp = fuzzy_comparator(rtol=rtol, atol=atol)
        fd = digest_batch_for(cmp)
        base = [0.0, 3.0, 1234.5678, -1234.5678, 7e8]
        outs = []
        for b in base:
            outs += [b, b]  # identical replicas
            outs.append(b + max(10.0 * atol, abs(b) * max(rtol, 1e-9) * 1e3) + 1.0)
        d = fd(outs)
        for i in range(len(outs)):
            for j in range(len(outs)):
                if outs[i] == outs[j]:
                    assert d[i] == d[j]
                elif cmp(outs[i], outs[j]) != cmp(outs[j], outs[i]):
                    continue  # asymmetric edge of isclose: no contract
                elif not cmp(outs[i], outs[j]):
                    assert d[i] != d[j], (outs[i], outs[j])

    def test_fuzzy_matrix_path_matches_scalar_groups(self):
        cmp = fuzzy_comparator(rtol=1e-6, atol=1e-9)
        fd = digest_batch_for(cmp)
        rs = np.random.RandomState(1)
        truth = rs.standard_normal(64).astype(np.float32)
        other = truth + rs.uniform(1, 2, 64).astype(np.float32)
        d = fd([truth, truth.copy(), other, truth.copy(), other.copy()])
        assert d[0] == d[1] == d[3]
        assert d[2] == d[4]
        assert d[0] != d[2]

    def test_fuzzy_nan_and_inf(self):
        fd = digest_batch_for(fuzzy_comparator(rtol=1e-6, atol=1e-9))
        inf = float("inf")
        d = fd([inf, inf, -inf, float("nan"), float("nan")])
        assert d[0] == d[1] != d[2]
        assert d[3] != d[4]  # NaN matches nothing
        # array payloads containing NaN match nothing either
        a = np.array([1.0, np.nan])
        d2 = fd([a, a.copy()])
        assert d2[0] != d2[1]

    def test_digest_hook_absent_for_unsupported_comparators(self):
        assert digest_batch_for(fuzzy_comparator(max_bad_fraction=0.05)) is None
        assert digest_batch_for(lambda a, b: True) is None
        assert digest_batch_for(None) is bitwise_digest_batch


# ---------------------------------------------------------------------------
# array-backed reputation table: batched ops == sequential ops
# ---------------------------------------------------------------------------


class TestAdaptiveBatchOps:
    def test_apply_events_matches_sequential(self):
        rng = random.Random(7)
        for trial in range(60):
            a = AdaptiveReplication(threshold=3, seed=trial)
            b = AdaptiveReplication(threshold=3, seed=trial)
            pre = [
                (rng.randrange(5), rng.randrange(3), rng.random() < 0.8)
                for _ in range(rng.randrange(20))
            ]
            for h, v, ok in pre:
                (a.on_validated if ok else a.on_invalid)(h, v)
                (b.on_validated if ok else b.on_invalid)(h, v)
            ev = [
                (rng.randrange(5), rng.randrange(3), rng.random() < 0.7)
                for _ in range(rng.randrange(1, 30))
            ]
            for h, v, ok in ev:
                (a.on_validated if ok else a.on_invalid)(h, v)
            b.apply_events([e[0] for e in ev], [e[1] for e in ev], [e[2] for e in ev])
            assert a.consecutive_valid == b.consecutive_valid, trial

    def test_should_replicate_batch_consumes_same_stream(self):
        """Batched decisions pop the identical RNG stream as per-call use,
        regardless of how many draws were prefetched."""
        rng = random.Random(1)
        for prefetch in (0, 3, 50):
            a = AdaptiveReplication(threshold=2, seed=9)
            b = AdaptiveReplication(threshold=2, seed=9)
            pairs = [(rng.randrange(4), rng.randrange(2)) for _ in range(30)]
            for h, v in pairs[:10]:
                a.on_validated(h, v)
                b.on_validated(h, v)
            seq = [a.should_replicate(h, v) for h, v in pairs]
            b.prefetch_draws(prefetch)
            assert list(b.should_replicate_batch(
                [p[0] for p in pairs], [p[1] for p in pairs]
            )) == seq

    def test_reputation_gathers(self):
        a = AdaptiveReplication(threshold=10)
        for _ in range(12):
            a.on_validated(1, 7)
        a.on_validated(2, 7)
        reps = a.reputations([1, 2, 99], [7, 7, 7])
        assert list(reps) == [12, 1, 0]  # unknown pairs read 0
        probs = a.replication_probabilities([1, 2, 99], [7, 7, 7])
        assert probs[0] == a.replication_probability(1, 7) < 1.0
        assert probs[1] == probs[2] == 1.0


# ---------------------------------------------------------------------------
# validation-pending index
# ---------------------------------------------------------------------------


class TestValidationPendingIndex:
    def test_index_tracks_fresh_successes(self):
        store, tr = build_pending(n_jobs=10, bad_frac=0.0)
        job_ids = set(store.jobs)
        assert store.pending_validation() == job_ids
        # oracle scan agrees
        store.use_indexes = False
        assert store.pending_validation() == job_ids
        store.use_indexes = True
        # validation consumes the freshness
        tr.tick(60.0)
        assert store.pending_validation() == set()
        store.check_invariants()

    def test_index_sharded(self):
        store, _ = build_pending(n_jobs=10, bad_frac=0.0)
        shard0 = store.pending_validation(0, 2)
        shard1 = store.pending_validation(1, 2)
        assert shard0 | shard1 == set(store.jobs)
        assert not shard0 & shard1

    def test_index_survives_mutation_paths(self):
        store, _ = build_pending(n_jobs=4, bad_frac=0.0)
        inst = next(iter(store.instances.values()))
        # un-succeeding an instance removes freshness
        inst.outcome = InstanceOutcome.CLIENT_ERROR
        store.check_invariants()
        inst.outcome = InstanceOutcome.SUCCESS
        store.check_invariants()
        inst.validate_state = ValidateState.INCONCLUSIVE
        store.check_invariants()
        inst.validate_state = ValidateState.INIT
        job = store.jobs[inst.job_id]
        store.purge_job(job)
        assert job.id not in store.pending_validation()
        store.check_invariants()
