"""Scenario matrix (§3.4, §9): trace-driven & adversarial populations.

PRs 1–5 guarded the engines with 7 hand-written synthetic scenarios. This
matrix replaces them with ~24 declarative :class:`ScenarioSpec` cases —
the originals ported verbatim, plus trace-replayed availability (diurnal
timezone waves, heavy-tailed sessions, correlated outages fitted from the
bundled ``host_sessions.csv`` trace) and the hostile populations §3.4's
replication/adaptive-validation design exists to defeat: colluding
cliques, Sybil churn-and-rejoin identities, credit farmers,
availability-correlated failures.

Every case runs through :func:`repro.core.run_parity`: the batch
validation engine vs the scalar oracle AND the vectorized world loop vs
the scalar event loop must produce identical SimMetrics, server counts,
credit totals, per-instance validate states, and job states — then the
scenario's golden bounds are checked on the (provably shared) result.
All scenarios are deterministic from their spec's seed.

Key empirical finding pinned here (seed_sweep_* + clique_half_fleet):
quorum-2 replication rejects every fabricated result from *independent*
cheaters, and a 3-of-12 clique on an always-on fleet never wins — but
once availability starvation (trace replay) or clique mass (≥ half the
fleet) concentrates both replicas of a job inside the clique, matching
wrong payloads validate each other and quorum is defeated. Adaptive
replication does NOT close this hole; the §3.4 defense layer
(``DefensePolicy``: work-spreading suspicion clusters + HR classes +
host punishment) does — the ``*_defended`` scenarios pin the contained
bounds, and ``test_clique_defense_regression`` pins both sides of the
flip. The residual wrong-accepts in the defended goldens are wins
*finalized before the first suspicion signal exists* (hosts buffer a
day of work in the initial placement burst, long before any validation
completes); a reactive defense cannot reach those, and the bound is
pinned so a regression in either direction is loud.
"""
import json

import pytest

from repro.core import (
    Clique,
    CreditFarm,
    DefensePolicy,
    Outage,
    ScenarioSpec,
    Sybil,
    TraceReplay,
    ValidateState,
    run_parity,
    run_spec,
    sybil_identity_ids,
)
from repro.core.scenarios import DAY, HOUR, SYBIL_ID_BASE, generate_population
from repro.data import toggles_to_intervals


def _assert_report_is_json(result):
    """``ScenarioResult.report()`` is plain JSON data named after its spec."""
    assert json.loads(json.dumps(result.report()))["name"] == result.spec.name


# ---------------------------------------------------------------------------
# the matrix: spec -> golden-bound check (run via the 3-axis parity harness)
# ---------------------------------------------------------------------------

SCENARIOS = {}


def scenario(spec):
    def register(check):
        assert spec.name not in SCENARIOS
        SCENARIOS[spec.name] = (spec, check)
        return check
    return register


# -- ported originals (PRs 1-5's hand-written matrix, now spec-declared) --

@scenario(ScenarioSpec(name="quiescence", horizon=3 * DAY))
def _check_quiescence(r):
    """Clean dedicated grid, generous horizon: everything validates and the
    plant goes quiescent at the quorum-2 overhead floor."""
    counts = r.server.counts()
    assert counts["jobs_success"] == 60
    assert counts["jobs_failure"] == 0
    assert r.metrics.error_rate == 0.0
    assert 2.0 <= r.metrics.replication_overhead <= 2.3
    assert counts["instances_in_progress"] == 0
    assert counts["instances_unsent"] == 0
    assert r.metrics.idle_fraction > 0.5


@scenario(ScenarioSpec(name="high_churn", n_hosts=16, churn_rate=1.0 / (1.5 * DAY),
                       horizon=5 * DAY, delay_bound=8 * HOUR, est_hours=1.5))
def _check_high_churn(r):
    """Hosts permanently depart mid-run (§4): deadlines fire, retries land
    on survivors, the work completes at an overhead premium."""
    assert r.server.counts()["jobs_success"] >= 56
    assert r.metrics.error_rate == 0.0
    assert 2.0 <= r.metrics.replication_overhead <= 2.5
    assert len(r.sim.specs) < 8  # most of the fleet actually left
    assert sum(t.metrics.timeouts for t in r.server.transitioners) > 0


@scenario(ScenarioSpec(name="malicious_independent", malicious_fraction=0.05,
                       error_prob=0.01, horizon=3 * DAY))
def _check_malicious_independent(r):
    """5% *independently* malicious volunteers (§3.4): quorum-2 replication
    rejects every fabricated result (contrast with the clique cases)."""
    assert r.metrics.wrong_accepted == 0
    assert r.metrics.error_rate == 0.0
    assert r.server.counts()["jobs_success"] >= 55
    assert r.metrics.replication_overhead > 2.0


@scenario(ScenarioSpec(name="cpu_gpu_mix", gpu=True, gpu_fraction=0.5,
                       n_jobs=80, est_hours=0.4))
def _check_cpu_gpu_mix(r):
    """Mixed CPU/GPU fleet (§3.1 plan classes) validates cross-device via
    the fuzzy comparator."""
    assert r.server.counts()["jobs_success"] == 80
    assert r.metrics.error_rate == 0.0
    gpu_versions = {
        v.id for v in r.server.store.apps["w"].versions
        if v.plan_class.name.startswith("gpu")
    }
    assert any(i.app_version_id in gpu_versions
               for i in r.server.store.instances.values())


@scenario(ScenarioSpec(name="low_availability", availability=0.6, horizon=4 * DAY))
def _check_low_availability(r):
    """~60% exponential availability (§1.1): throughput drops, correctness
    holds."""
    assert r.server.counts()["jobs_success"] >= 55
    assert r.metrics.error_rate == 0.0
    assert r.metrics.idle_fraction >= 0.35


@scenario(ScenarioSpec(name="error_prone", error_prob=0.05, horizon=3 * DAY))
def _check_error_prone(r):
    """Flaky hardware corrupting 5% of results: replication filters all of
    it."""
    assert r.metrics.wrong_accepted == 0
    assert r.server.counts()["jobs_success"] >= 55
    assert r.metrics.replication_overhead > 2.0
    assert any(i.validate_state == ValidateState.INVALID
               for i in r.server.store.instances.values())


# -- trace-driven availability (repro.data.traces replay) --

@scenario(ScenarioSpec(name="trace_diurnal_3tz", seed=5,
                       trace=TraceReplay(n_timezones=3), horizon=3 * DAY))
def _check_trace_diurnal_3tz(r):
    """Replayed trace availability, 3 timezone waves: the fleet is online
    ~2/3 of the time in rolling waves; work still completes cleanly."""
    assert all(s.avail_schedule is not None for s in r.population)
    assert r.server.counts()["jobs_success"] == 60
    assert r.metrics.wrong_accepted == 0
    assert 2.0 <= r.metrics.replication_overhead <= 2.2
    assert r.metrics.idle_fraction > 0.9


@scenario(ScenarioSpec(name="trace_single_tz", seed=6,
                       trace=TraceReplay(n_timezones=1), horizon=3 * DAY))
def _check_trace_single_tz(r):
    """One timezone: the whole fleet sleeps together — the worst-case
    diurnal trough — and the backlog still drains by the horizon."""
    assert r.server.counts()["jobs_success"] == 60
    assert r.metrics.wrong_accepted == 0
    assert r.metrics.replication_overhead <= 2.3


@scenario(ScenarioSpec(name="trace_heavy_tail", seed=7,
                       trace=TraceReplay(diurnal=False, scale=0.6), horizon=3 * DAY))
def _check_trace_heavy_tail(r):
    """Heavy-tailed lognormal sessions without the diurnal wave (pure
    session-length effect), compressed 0.6x for faster mixing."""
    assert r.server.counts()["jobs_success"] == 60
    assert r.metrics.wrong_accepted == 0
    assert r.metrics.replication_overhead <= 2.2


@scenario(ScenarioSpec(name="trace_outage", seed=5, trace=TraceReplay(n_timezones=3),
                       outage=Outage(start=0.75 * DAY, duration=6 * HOUR, fraction=0.5),
                       horizon=3 * DAY))
def _check_trace_outage(r):
    """Correlated outage on top of trace replay: half the fleet loses power
    simultaneously for 6h; the schedule splice keeps them all dark."""
    spec = r.spec
    dark = [s for s in r.population
            if not any(a < spec.outage.start + spec.outage.duration
                       and b > spec.outage.start
                       for a, b in toggles_to_intervals(s.avail_schedule, spec.horizon))]
    assert len(dark) >= spec.n_hosts // 2  # the hit half plus chance sleepers
    assert r.server.counts()["jobs_success"] == 60
    assert r.metrics.wrong_accepted == 0


@scenario(ScenarioSpec(name="blackout_half", seed=3,
                       outage=Outage(start=1.0 * DAY, duration=8 * HOUR, fraction=0.5),
                       horizon=3 * DAY))
def _check_blackout_half(r):
    """Outage layer on an otherwise always-on fleet: exactly the hit half
    gets a forced 8h window, everyone else never toggles."""
    scheduled = [s for s in r.population if s.avail_schedule is not None]
    assert len(scheduled) == 6
    assert all(s.avail_schedule == (1.0 * DAY, 1.0 * DAY + 8 * HOUR)
               for s in scheduled)
    assert r.server.counts()["jobs_success"] == 60
    assert r.metrics.wrong_accepted == 0


@scenario(ScenarioSpec(name="trace_adaptive", seed=5, trace=TraceReplay(n_timezones=2),
                       adaptive=True, n_jobs=80, horizon=3 * DAY))
def _check_trace_adaptive(r):
    """Adaptive replication under realistic availability: overhead still
    trends toward the §3.4 target without accepting errors."""
    assert r.server.counts()["jobs_success"] == 80
    assert r.metrics.wrong_accepted == 0
    assert r.metrics.replication_overhead <= 2.2


@scenario(ScenarioSpec(name="correlated_failures", seed=8,
                       trace=TraceReplay(n_timezones=3),
                       correlated_failures=0.3, horizon=3 * DAY))
def _check_correlated_failures(r):
    """Failures correlated with poor availability: the least-available
    quartile also corrupts 30% of its results (failing flash, dying PSU)."""
    flaky = [s for s in r.population if s.error_prob == 0.3]
    assert len(flaky) == r.spec.n_hosts // 4
    assert r.server.counts()["jobs_success"] == 60
    assert r.metrics.wrong_accepted == 0
    assert r.metrics.replication_overhead > 2.0  # corruption forced retries


# -- adversarial populations --

@scenario(ScenarioSpec(name="clique_pair", seed=2, clique=Clique(size=2), n_jobs=40))
def _check_clique_pair(r):
    """2-host clique vs quorum-2 on an always-on 12-host fleet: the
    scheduler's one-instance-per-host rule means both replicas must land on
    the 2 cliquers — never happens here; zero credit leaks."""
    assert r.metrics.wrong_accepted == 0
    assert r.clique_quorum_wins() == 0
    assert r.credit_of_hosts(r.clique_host_ids()) == 0.0
    assert r.server.counts()["jobs_success"] == 40


@scenario(ScenarioSpec(name="clique_triple_adaptive", seed=2, adaptive=True,
                       clique=Clique(size=3), n_jobs=40))
def _check_clique_triple_adaptive(r):
    """Satellite regression: 3-host clique with matching wrong payloads vs
    min_quorum=2 honest replicas, adaptive replication ON. Current
    behavior: always-cheating cliquers never build reputation, every job
    still replicates, and no wrong result wins quorum."""
    assert r.metrics.wrong_accepted == 0
    assert r.clique_quorum_wins() == 0
    assert r.credit_of_hosts(r.clique_host_ids()) == 0.0
    assert r.wrong_credit() == 0.0
    assert r.server.counts()["jobs_success"] == 40


@scenario(ScenarioSpec(name="clique_half_fleet", seed=2, clique=Clique(size=6),
                       n_jobs=40))
def _check_clique_half_fleet(r):
    """6-of-12 clique, defense OFF: with half the fleet colluding, both
    replicas of a job frequently land inside the clique and the matching
    wrong payloads validate each other — quorum is structurally defeated
    (seed-pinned golden; clique_half_fleet_defended pins the fix)."""
    assert r.metrics.wrong_accepted == 9
    assert r.clique_quorum_wins() == 9
    assert 0.0 < r.wrong_credit() <= 8.0
    assert r.server.counts()["jobs_success"] == 40


@scenario(ScenarioSpec(name="clique_half_fleet_defended", seed=2,
                       clique=Clique(size=6), n_jobs=40,
                       defense=DefensePolicy()))
def _check_clique_half_fleet_defended(r):
    """The flip: same 6-of-12 clique with the §3.4 defense layer ON. The
    clique's co-wins + losses against honest pairs turn its active members
    suspicious and cluster them; from then on same-cluster replicas count
    as ONE vote toward quorum, so every later collusion attempt is vetoed
    and re-validated against an honest tie-breaker. 9 defeated quorums
    drop to 1 — the single win finalized before the first loss signal
    existed (initial placement burst at t≈140, first validation t≈3420;
    see the module docstring for why that residual is structural)."""
    assert r.metrics.wrong_accepted == 1
    assert r.clique_quorum_wins() == 1
    assert 0.0 < r.wrong_credit() <= 1.0
    assert r.server.counts()["jobs_success"] == 40
    assert r.server.counts()["jobs_failure"] == 0
    d = r.report()["defense"]
    # why: the active clique pair clustered, and punishment bit too
    assert d["n_clusters"] >= 1
    assert set(d["clique_hosts_clustered"]) <= set(r.clique_host_ids())
    assert len(d["clique_hosts_clustered"]) >= 2
    assert d["quota_denials"] + d["clique_deferrals"] > 0


@scenario(ScenarioSpec(name="honest_fleet_defended", seed=12, n_hosts=1000,
                       n_jobs=300, horizon=0.5 * DAY, est_hours=0.05,
                       availability=0.9, defense=DefensePolicy()))
def _check_honest_fleet_defended(r):
    """1,000 honest hosts with the whole defense stack on: every job
    drains and nothing clusters, so the defense costs no liveness at fleet
    scale (the small-fleet corners are test_defense_never_deadlocks_*)."""
    counts = r.server.counts()
    assert counts["jobs_success"] == 300
    assert counts["jobs_failure"] == 0
    assert counts["instances_unsent"] == 0
    assert r.metrics.wrong_accepted == 0
    assert r.report()["defense"]["n_clusters"] == 0


@scenario(ScenarioSpec(name="clique_small_fleet", seed=2, n_hosts=6,
                       clique=Clique(size=3), n_jobs=40))
def _check_clique_small_fleet(r):
    """3-of-6 clique — same story at half scale (seed-pinned golden)."""
    assert r.metrics.wrong_accepted == 4
    assert r.clique_quorum_wins() == 4
    assert r.server.counts()["jobs_success"] == 40


@scenario(ScenarioSpec(name="clique_small_fleet_defended", seed=2, n_hosts=6,
                       clique=Clique(size=3), n_jobs=40,
                       defense=DefensePolicy()))
def _check_clique_small_fleet_defended(r):
    """Honest negative result, pinned: at 6 hosts the defense does NOT
    beat the defense-off baseline (7 wrong vs 4). HR pinning fragments a
    tiny fleet into 2–3-host classes, and when a class is exactly the
    clique pair they only ever validate each other — the accomplice rule
    eventually clusters them (one partner never loses, so suspicion alone
    can't), but the early class-confined wins are already final. Work
    still completes (the HR relax sweep unpins stuck jobs) and the spread
    veto is live once the cluster forms. Pinned so the tiny-fleet HR
    hazard stays visible rather than averaged away."""
    assert r.metrics.wrong_accepted == 7
    assert r.clique_quorum_wins() == 7
    assert r.server.counts()["jobs_success"] == 40
    assert r.server.counts()["jobs_failure"] == 0
    d = r.report()["defense"]
    assert d["n_clusters"] >= 1
    assert d["spread_denials"] > 0  # the veto did engage post-clustering
    assert d["hr_relaxations"] > 0  # ...and the relax sweep kept work flowing


@scenario(ScenarioSpec(name="sybil_rejoin", seed=4, adaptive=True,
                       sybil=Sybil(), n_jobs=40, waves=8, wave_period=6 * HOUR))
def _check_sybil_rejoin(r):
    """Sybil churn-and-rejoin under adaptive replication: the fresh
    identity presents, gets work, and earns nothing (deep purge-path
    asserts live in test_sybil_rejoin_regression)."""
    new_id = sybil_identity_ids(r.spec)[0]
    assert new_id in r.sim.world.index
    assert any(i.host_id == new_id for i in r.server.store.instances.values())
    assert r.metrics.wrong_accepted == 0
    assert r.wrong_credit() == 0.0
    assert r.server.counts()["jobs_success"] == 40


@scenario(ScenarioSpec(name="sybil_serial", seed=4, adaptive=True, n_jobs=60,
                       horizon=3 * DAY, waves=12, wave_period=6 * HOUR,
                       sybil=Sybil(churn_at=0.5 * DAY, rejoin_at=0.75 * DAY,
                                   rejoins=3, period=0.5 * DAY)))
def _check_sybil_serial(r):
    """Serial Sybil: three fresh identities in sequence, each shedding the
    last one's (non-)reputation. Each gets work; none of them ever wins."""
    ids = sybil_identity_ids(r.spec)
    assert len(ids) == 3
    assert all(i in r.sim.world.index for i in ids)
    by_host = {i: 0 for i in ids}
    for inst in r.server.store.instances.values():
        if inst.host_id in by_host:
            by_host[inst.host_id] += 1
    assert all(n > 0 for n in by_host.values())
    assert r.metrics.wrong_accepted == 0
    assert r.credit_of_hosts(ids) == 0.0
    assert r.server.counts()["jobs_success"] == 60


@scenario(ScenarioSpec(name="credit_farm", seed=9, farm=CreditFarm(count=2, factor=8.0),
                       n_jobs=40, horizon=3 * DAY))
def _check_credit_farm(r):
    """Credit farmers inflate claimed PFC 8x while computing correctly.
    §7's claim normalization + outlier-robust granting means the inflation
    does NOT pay: per-farmer credit stays at/below the honest mean."""
    farm = r.farm_host_ids()
    assert len(farm) == 2
    per_farmer = r.credit_of_hosts(farm) / len(farm)
    honest = r.mean_honest_host_credit()
    assert 0.0 < per_farmer <= 1.5 * honest
    # the residual lie is still visible (claimed > granted on farmer
    # instances) but §7's host normalization has already absorbed most of
    # the 8x inflation before granting even sees it
    claimed = granted = 0.0
    for i in r.server.store.instances.values():
        if i.host_id in farm:
            claimed += i.claimed_credit
            granted += max(0.0, i.granted_credit)
    assert 1.3 * granted < claimed < 3.0 * granted
    assert r.metrics.wrong_accepted == 0
    assert r.server.counts()["jobs_success"] == 40


@scenario(ScenarioSpec(name="farm_adaptive", seed=9, adaptive=True,
                       farm=CreditFarm(count=3, factor=16.0), error_prob=0.01,
                       n_jobs=60, horizon=3 * DAY))
def _check_farm_adaptive(r):
    """16x farmers under adaptive replication on a mildly flaky fleet:
    still no payoff."""
    farm = r.farm_host_ids()
    assert len(farm) == 3
    per_farmer = r.credit_of_hosts(farm) / len(farm)
    assert 0.0 < per_farmer <= 1.5 * r.mean_honest_host_credit()
    assert r.metrics.wrong_accepted == 0
    assert r.server.counts()["jobs_success"] == 60


@scenario(ScenarioSpec(name="kitchen_sink", seed=10, trace=TraceReplay(n_timezones=3),
                       clique=Clique(size=3), farm=CreditFarm(count=2, factor=8.0),
                       correlated_failures=0.2, churn_rate=1.0 / (6 * DAY),
                       horizon=3 * DAY, n_jobs=60))
def _check_kitchen_sink(r):
    """Everything at once: trace waves + churn + correlated failures +
    clique + farmers. Work completes; adversarial leakage stays bounded."""
    counts = r.server.counts()
    assert counts["jobs_success"] == 60
    assert counts["jobs_failure"] == 0
    assert len(r.sim.specs) < r.spec.n_hosts  # churn happened
    assert r.metrics.wrong_accepted <= 4  # availability-starved clique wins a few
    assert r.clique_quorum_wins() == r.metrics.wrong_accepted
    assert r.wrong_credit() <= 2.0


# -- seed sweep: same spec shape, different seeds; golden bounds hold, and
#    the availability-starvation quorum defeat reproduces at every seed --

def _check_starved_clique(r):
    """Trace-driven availability + 3-host clique: replicas concentrate on
    whoever is online, so both copies of a job often land inside the
    always-cheating clique — quorum defeated without clique majority. The
    defense gap is pinned (exact counts are seed-golden, asserted identical
    across all three engines by the parity harness)."""
    assert r.server.counts()["jobs_success"] == 40
    assert r.server.counts()["jobs_failure"] == 0
    assert r.clique_quorum_wins() == r.metrics.wrong_accepted
    assert r.wrong_credit() > 0.0
    assert 2.0 <= r.metrics.replication_overhead <= 3.2


for _seed, _wins in ((7, 12), (11, 23)):
    @scenario(ScenarioSpec(name=f"starved_clique_seed{_seed}", seed=_seed,
                           trace=TraceReplay(n_timezones=3), clique=Clique(size=3),
                           horizon=3 * DAY, n_jobs=40))
    def _check(r, _wins=_wins):
        _check_starved_clique(r)
        assert r.metrics.wrong_accepted == _wins


# The flip for the availability-starved variant: same trace-driven specs
# with the defense ON. 12 and 23 defeated quorums both contain to 4 — the
# wins finalized before the clique's first loss turned any member
# suspicious (the structural residual; module docstring). Everything after
# the cluster forms is vetoed by the effective-quorum rule.
for _seed in (7, 11):
    @scenario(ScenarioSpec(name=f"starved_clique_seed{_seed}_defended",
                           seed=_seed, trace=TraceReplay(n_timezones=3),
                           clique=Clique(size=3), horizon=3 * DAY, n_jobs=40,
                           defense=DefensePolicy()))
    def _check_defended(r):
        assert r.metrics.wrong_accepted == 4
        assert r.clique_quorum_wins() == 4
        assert r.server.counts()["jobs_success"] == 40
        assert r.server.counts()["jobs_failure"] == 0
        assert 2.0 <= r.metrics.replication_overhead <= 3.2
        d = r.report()["defense"]
        assert d["n_clusters"] >= 1
        assert len(d["clique_hosts_clustered"]) >= 2


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_matrix(name):
    spec, check = SCENARIOS[name]
    result = run_parity(spec)
    assert len(result.population) == spec.n_hosts
    _assert_report_is_json(result)
    check(result)


# ---------------------------------------------------------------------------
# §3.4's core claim, end to end (ported): adaptive replication cuts the
# overhead toward 1 while the accepted-error rate stays bounded.
# ---------------------------------------------------------------------------

def test_adaptive_vs_plain_replication():
    base = dict(n_jobs=360, n_hosts=20, horizon=6 * DAY, error_prob=0.005,
                waves=12)
    plain = run_parity(ScenarioSpec(name="waves_plain", **base))
    adaptive = run_parity(ScenarioSpec(name="waves_adaptive", adaptive=True, **base))
    _assert_report_is_json(plain)
    _assert_report_is_json(adaptive)
    assert plain.metrics.replication_overhead >= 2.0
    assert adaptive.metrics.replication_overhead < plain.metrics.replication_overhead
    assert adaptive.metrics.replication_overhead < 1.9
    assert adaptive.metrics.error_rate <= 0.02
    assert adaptive.metrics.correct_accepted >= 330


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_clique_defense_regression():
    """Pin the quorum-defeat boundary (§3.4). A 3-of-12 always-cheating
    clique with matching payloads cannot beat min_quorum=2 + adaptive
    replication: cheaters never validate, so they never become reputable,
    so their jobs keep getting replicated onto honest hosts. But the
    defense is structural, not reputational — once the clique covers
    enough of the *online* fleet (half the hosts here; or a trace-starved
    fleet, see starved_clique_seed*), both replicas land inside it and
    matching wrong payloads win.

    Both sides of the boundary are pinned: defense OFF, a 6-of-12 clique
    holds at 9 defeated quorums / <=8 credit leaked (seed 2) — adaptive
    replication alone never closes this. Defense ON (DefensePolicy: §3.4
    work-spreading clusters + HR classes + host punishment), the same
    clique contains to exactly 1 — the single pre-signal win. The
    defended golden lives in clique_half_fleet_defended; here we pin the
    *gap* so neither side can silently drift."""
    safe = run_spec(ScenarioSpec(name="clique_triple_adaptive_reg", seed=2,
                                 adaptive=True, clique=Clique(size=3), n_jobs=40))
    assert safe.metrics.wrong_accepted == 0
    assert safe.clique_quorum_wins() == 0
    assert safe.credit_of_hosts(safe.clique_host_ids()) == 0.0
    # every clique result that reached validation was marked INVALID
    clique = set(safe.clique_host_ids())
    judged = [i for i in safe.server.store.instances.values()
              if i.host_id in clique
              and i.validate_state in (ValidateState.VALID, ValidateState.INVALID)]
    assert judged and all(i.validate_state == ValidateState.INVALID for i in judged)

    broken = run_spec(ScenarioSpec(name="clique_half_fleet_reg", seed=2,
                                   clique=Clique(size=6), n_jobs=40))
    assert broken.metrics.wrong_accepted == 9  # the vulnerability, pinned
    assert 0.0 < broken.wrong_credit() <= 8.0

    defended = run_spec(ScenarioSpec(name="clique_half_fleet_def_reg", seed=2,
                                     clique=Clique(size=6), n_jobs=40,
                                     defense=DefensePolicy()))
    assert defended.metrics.wrong_accepted == 1  # the fix, pinned
    assert defended.wrong_credit() < broken.wrong_credit()
    assert defended.server.counts()["jobs_success"] == 40


def test_sybil_rejoin_regression():
    """Satellite regression: churn a malicious host, rejoin it under a new
    host id. The purge paths must not leak the old identity, and the new
    identity must restart untrusted."""
    spec = ScenarioSpec(name="sybil_rejoin_reg", seed=4, adaptive=True,
                        sybil=Sybil(), n_jobs=40, waves=8,
                        wave_period=6 * HOUR)
    r = run_spec(spec)
    old_id = spec.sybil.host_index + 1  # make_population ids are 1-based
    new_id = sybil_identity_ids(spec)[0]
    assert new_id == SYBIL_ID_BASE + 1
    server, sim = r.server, r.sim

    # old identity fully purged server-side (server.remove_host paths)
    assert old_id not in server.store.hosts
    assert old_id not in server.estimator._host_versions
    assert all(server.adaptive.reputation(old_id, v.id) == 0
               for v in server.store.apps["w"].versions)
    assert all(h != old_id for h, _ in server.adaptive.consecutive_valid)
    assert old_id not in sim.specs and old_id not in sim.clients

    # ... but its world slot is tombstoned, never recycled: presenting the
    # same id again is impossible, which is what forces the Sybil to shed
    # its reputation along with its identity
    assert old_id in sim.world.index
    assert not sim.world.alive[sim.world.index[old_id]]

    # the fresh identity registered, got work, and restarted untrusted
    assert new_id in sim.specs and new_id in server.store.hosts
    new_instances = [i for i in server.store.instances.values()
                     if i.host_id == new_id]
    assert new_instances
    assert all(server.adaptive.reputation(new_id, v.id) == 0
               for v in server.store.apps["w"].versions)
    # always-cheating under quorum-2: every judged result INVALID, no credit
    judged = [i for i in new_instances
              if i.validate_state in (ValidateState.VALID, ValidateState.INVALID)]
    assert judged and all(i.validate_state == ValidateState.INVALID for i in judged)
    assert server.credit.total.get(f"host:{new_id}", 0.0) == 0.0
    assert r.metrics.wrong_accepted == 0

    # with the defense layer ON, the purge must be just as airtight: the
    # churned identity leaves no agreement stats, suspicion, cluster
    # membership, backoff, HR census entry, or live quota row behind
    rd = run_spec(ScenarioSpec(**{**vars(spec), "name": "sybil_rejoin_def_reg",
                                  "defense": DefensePolicy()}))
    d = rd.server.defense
    assert old_id not in d._lost and old_id not in d._validated
    assert old_id not in d._agree
    assert all(old_id not in peers for peers in d._agree.values())
    assert old_id not in d.clusters()
    assert old_id not in d._backoff
    assert old_id not in d._hr_of_host
    for table in (d.denied_quota_by, d.denied_spread_by, d.deferred_by,
                  d.cancelled_by):
        assert old_id not in table
    hr = d._host_idx.get(old_id)
    if hr is not None:  # dense slot stays mapped; row must be factory-fresh
        assert (d.quota[hr, :] == d.policy.quota_init).all()
        assert (d.sent[hr, :] == 0).all()
    # the fresh identity still gets work and still earns nothing
    assert any(i.host_id == new_id for i in rd.server.store.instances.values())
    assert rd.server.credit.total.get(f"host:{new_id}", 0.0) == 0.0
    assert rd.metrics.wrong_accepted == 0


# ---------------------------------------------------------------------------
# defense liveness: placement constraints never deadlock. HR pinning and
# the spread veto *restrict* eligible hosts, so the hazard is a job whose
# eligible set goes empty forever; the relax sweeps (hr_relaxations /
# spread_relaxations) must guarantee drain on any honest fleet.
# ---------------------------------------------------------------------------

def _assert_defense_drains(seed, n_hosts, n_jobs, error_prob, with_trace):
    spec = ScenarioSpec(
        name="defense_drain", seed=seed, n_hosts=n_hosts, n_jobs=n_jobs,
        error_prob=error_prob,
        trace=TraceReplay(n_timezones=2) if with_trace else None,
        horizon=3 * DAY if with_trace else 2 * DAY,
        defense=DefensePolicy(),
    )
    r = run_spec(spec)
    c = r.server.counts()
    assert c["jobs_success"] == n_jobs, c
    assert c["jobs_failure"] == 0, c
    assert c["instances_unsent"] == 0, c  # nothing wedged behind a pin
    assert c["instances_in_progress"] == 0, c
    assert r.metrics.wrong_accepted == 0
    if error_prob == 0.0:
        # clean fleets never cluster; flaky ones may false-cluster on
        # small samples (co-INVALID pairs), which costs only overhead —
        # the drain asserts above are what prove it stays harmless
        assert r.report()["defense"]["n_clusters"] == 0


@pytest.mark.parametrize(
    "seed,n_hosts,n_jobs,error_prob,with_trace",
    [
        (0, 4, 8, 0.0, False),     # tiny fleet: HR classes are 1-2 hosts
        (1, 6, 12, 0.1, False),    # flaky: retries stress the quota table
        (2, 12, 20, 0.05, False),
        (3, 12, 16, 0.05, True),   # diurnal starvation + flaky
        (4, 5, 10, 0.15, True),    # tiny AND starved AND very flaky
    ],
)
def test_defense_never_deadlocks_corners(seed, n_hosts, n_jobs, error_prob,
                                         with_trace):
    """Deterministic corner sweep of the liveness contract (always runs,
    even without hypothesis installed)."""
    _assert_defense_drains(seed, n_hosts, n_jobs, error_prob, with_trace)


def test_defense_never_deadlocks():
    """Property (hypothesis): with the full defense stack ON and an
    all-honest fleet, every job reaches quorum and the queue drains —
    across fleet sizes, error rates, and trace-driven availability."""
    pytest.importorskip("hypothesis")  # optional dep: see requirements-dev.txt
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(min_value=0, max_value=10**6),
        n_hosts=st.integers(min_value=4, max_value=14),
        n_jobs=st.integers(min_value=4, max_value=20),
        error_prob=st.sampled_from([0.0, 0.02, 0.1]),
        with_trace=st.booleans(),
    )
    def prop(seed, n_hosts, n_jobs, error_prob, with_trace):
        _assert_defense_drains(seed, n_hosts, n_jobs, error_prob, with_trace)

    prop()


# ---------------------------------------------------------------------------
# generation purity: same (spec, seed) => identical populations, world
# columns, and event streams (hypothesis property, satellite 3)
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402

from repro.core.scenarios import build  # noqa: E402


def _spec_from(draw_seed, n_hosts, with_trace, with_clique, with_farm, with_sybil):
    return ScenarioSpec(
        name="prop", seed=draw_seed, n_hosts=n_hosts, n_jobs=8,
        trace=TraceReplay(n_timezones=2) if with_trace else None,
        clique=Clique(size=min(3, n_hosts - 1)) if with_clique else None,
        farm=CreditFarm(count=2) if with_farm else None,
        sybil=Sybil() if with_sybil else None,
        adaptive=with_sybil,
    )


def _pop_fields(pop):
    out = []
    for s in pop:
        d = dict(vars(s))
        h = d.pop("host")
        d["host"] = (h.id, h.platforms, h.cpu_vendor, h.cpu_model,
                     h.os_version, h.on_fraction, h.volunteer_id,
                     tuple((rt, r.ninstances, r.peak_flops, r.availability)
                           for rt, r in sorted(h.resources.items(),
                                               key=lambda kv: kv[0].value)))
        out.append(d)
    return out


def _assert_generation_pure(seed, n_hosts, with_trace, with_clique,
                            with_farm, with_sybil):
    spec = _spec_from(seed, n_hosts, with_trace, with_clique, with_farm,
                      with_sybil)
    # same spec twice: field-identical populations...
    assert _pop_fields(generate_population(spec)) == _pop_fields(
        generate_population(spec))
    # ...and identical constructed worlds: every HostArrays column and the
    # full pending event stream (heap entries are (t, seq, kind, host))
    _, sim_a, _ = build(spec)
    _, sim_b, _ = build(spec)
    wa, wb = sim_a.world, sim_b.world
    assert wa.index == wb.index
    for col in ("ids", "alive", "available", "flops", "cap_ncpu", "ram",
                "b_hi", "time_slice", "sched_ncpu"):
        assert np.array_equal(getattr(wa, col), getattr(wb, col)), col
    assert sorted(sim_a._heap) == sorted(sim_b._heap)
    # a different seed must actually move the population
    other = ScenarioSpec(**{**vars(spec), "seed": seed + 1})
    assert _pop_fields(generate_population(other)) != _pop_fields(
        generate_population(spec))


@pytest.mark.parametrize(
    "seed,n_hosts,with_trace,with_clique,with_farm,with_sybil",
    [
        (0, 4, False, False, False, False),
        (1, 12, True, False, False, False),
        (2, 12, False, True, False, False),
        (3, 12, False, False, True, False),
        (4, 12, False, False, False, True),
        (5, 8, True, True, True, False),
        (6, 14, True, True, True, True),
        (982451653, 5, True, False, True, True),
    ],
)
def test_generation_purity_corners(seed, n_hosts, with_trace, with_clique,
                                   with_farm, with_sybil):
    """Deterministic corner sweep of the purity contract (always runs,
    even without hypothesis installed)."""
    _assert_generation_pure(seed, n_hosts, with_trace, with_clique,
                            with_farm, with_sybil)


def test_generation_pure_in_spec_and_seed():
    """Property (hypothesis): scenario generation is a pure function of
    (spec, seed) across the whole layered spec space."""
    pytest.importorskip("hypothesis")  # optional dep: see requirements-dev.txt
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(min_value=0, max_value=10**6),
        n_hosts=st.integers(min_value=4, max_value=14),
        with_trace=st.booleans(),
        with_clique=st.booleans(),
        with_farm=st.booleans(),
        with_sybil=st.booleans(),
    )
    def prop(seed, n_hosts, with_trace, with_clique, with_farm, with_sybil):
        _assert_generation_pure(seed, n_hosts, with_trace, with_clique,
                                with_farm, with_sybil)

    prop()


# ---------------------------------------------------------------------------
# full-scale adversarial run (CI: behind the slow marker)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_adversarial_10k_hosts():
    """10k-host fleet with a 500-host clique, 200 farmers, churn, and an
    epoch-batched vectorized world: the engines hold at population scale.
    Engine-only (the 3-axis parity contract is already pinned per-scenario
    above; a scalar-oracle run at 10k hosts is minutes, not seconds)."""
    spec = ScenarioSpec(
        name="adversarial_10k", seed=12, n_hosts=10_000, n_jobs=3000,
        horizon=0.5 * DAY, est_hours=0.05, clique=Clique(size=500),
        farm=CreditFarm(count=200, factor=8.0), churn_rate=1.0 / (30 * DAY),
        availability=0.9,
    )
    r = run_spec(spec, epoch=60.0)
    _assert_report_is_json(r)
    counts = r.server.counts()
    assert counts["jobs_success"] >= 2900
    assert r.metrics.error_rate <= 0.01  # 5% clique: quorum holds at scale
    assert r.clique_quorum_wins() == r.metrics.wrong_accepted
    assert 2.0 <= r.metrics.replication_overhead <= 2.6
