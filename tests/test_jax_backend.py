"""NumPy ⇄ JAX backend parity (the 4th test-matrix axis, kernel-level).

The scenario matrix locks the JAX backend down end-to-end; these tests
attack the same contract from below with randomized inputs:

- property tests over the dispatch score/estimate kernels and the
  eligibility scan, against inline NumPy replicas of the engine's exact
  IEEE op order;
- fleet-level WRR / run-set identity between ``BatchClientEngine()`` and
  ``BatchClientEngine(backend="jax")`` on feature-dense random fleets;
- digest-bucket equality between the Pallas ``quorum_compare`` grouping
  and a ``quorum_compare_ref``-based greedy grouping across tolerance
  bands, including the -0.0 and NaN payload corners pinned in PR 4;
- dirty-upload regression: mutate hosts through every ``_touch`` hook
  between device ticks and assert the incrementally-uploaded device
  columns equal the host arrays (i.e. match a from-scratch upload), and
  that a NumPy twin world stays bitwise identical.

Each property is a function of one integer seed. A seeded sweep always
runs; when hypothesis is installed (requirements-dev.txt) the same
properties also run under its shrinking search.
"""
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

try:  # optional dep: see requirements-dev.txt
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import BatchClientEngine, ResourceType, jax_backend
from repro.core.client import (
    Client,
    ClientJob,
    ClientPrefs,
    ClientResource,
    ProjectAttachment,
    RunState,
)
from repro.core.jax_backend import (
    HAVE_JAX,
    dispatch_elig,
    dispatch_scores,
    fuzzy_digest_jax,
    quorum_group_codes,
    resolve_backend,
)
from repro.core.scheduler import W_BALANCE, W_KEYWORD, W_PRIORITY, W_SKIPPED
from repro.core.world import HostArrays
from repro.kernels.quorum_compare.ref import quorum_compare_ref
from test_batch_client import _assert_wrr_equal, make_clients

CPU = ResourceType.CPU

assert HAVE_JAX  # importorskip above guarantees it


def hyp(prop, **kw):
    """Attach a hypothesis seed-search twin of a seeded property test."""

    def deco(fn):
        if not HAVE_HYPOTHESIS:
            return None  # seeded sweep still covers the property
        return settings(deadline=None, **kw)(
            given(st.integers(0, 2**31 - 1))(fn)
        )

    return deco(prop)


def test_resolve_backend():
    assert resolve_backend("numpy") == "numpy"
    assert resolve_backend("jax") == "jax"
    with pytest.raises(ValueError):
        resolve_backend("torch")


# ---------------------------------------------------------------------------
# dispatch kernels vs inline NumPy replicas
# ---------------------------------------------------------------------------


def _prop_dispatch_scores(seed):
    """Device score/est/scaled == the engine's NumPy branch, bit for bit
    (same accumulation order; sparse-division-by-positive-pf pattern)."""
    rs = np.random.RandomState(seed)
    n = int(rs.randint(1, 65))
    kvec = rs.rand(n) < 0.5
    bal = rs.uniform(-10, 10, n) if rs.rand() < 0.5 else None
    prio = rs.uniform(-5, 5, n)
    skips = rs.randint(0, 9, n).astype(np.float64)
    flop = rs.uniform(1e9, 1e14, n)
    pf = np.where(rs.rand(n) < 0.2, 0.0, rs.uniform(1e8, 1e11, n))
    avail = float(rs.choice([0.0, 0.35, 1.0]))

    # inline replica of BatchDispatchEngine.candidate_rows' numpy branch
    scores = W_KEYWORD * kvec
    if bal is not None:
        scores += W_BALANCE * bal
    scores += W_PRIORITY * prio
    scores += W_SKIPPED * np.minimum(skips, 5.0)
    est = np.full(n, np.inf, dtype=np.float64)
    pos = pf > 0.0
    est[pos] = flop[pos] / pf[pos]
    if avail <= 0:
        scaled = np.full(n, np.inf, dtype=np.float64)
    else:
        scaled = est / avail

    js, je, jx = dispatch_scores(
        kvec, bal, prio, skips, flop, pf, avail,
        (W_KEYWORD, W_BALANCE, W_PRIORITY, W_SKIPPED),
    )
    assert np.array_equal(js, scores)
    assert np.array_equal(je, est)
    assert np.array_equal(jx, scaled)


def _prop_dispatch_elig(seed):
    """Rotated eligibility scan == the NumPy roll/compare pipeline."""
    rs = np.random.RandomState(seed)
    n = int(rs.randint(1, 129))
    valid = rs.rand(n) < 0.7
    target = np.where(rs.rand(n) < 0.6, -1, rs.randint(1, 5, n)).astype(np.int64)
    start = int(rs.randint(0, n))
    host_id = int(rs.randint(1, 5))
    tv = np.roll(valid, -start)
    tt = np.roll(target, -start)
    want = tv & ((tt < 0) | (tt == host_id))
    got = dispatch_elig(valid, target, start, host_id)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("seed", range(30))
def test_dispatch_scores_matches_numpy(seed):
    _prop_dispatch_scores(seed)


@pytest.mark.parametrize("seed", range(20))
def test_dispatch_elig_matches_numpy(seed):
    _prop_dispatch_elig(seed)


test_dispatch_scores_hypothesis = hyp(_prop_dispatch_scores, max_examples=60)
test_dispatch_elig_hypothesis = hyp(_prop_dispatch_elig, max_examples=40)


# ---------------------------------------------------------------------------
# client engine: WRR + run-set identity on random fleets
# ---------------------------------------------------------------------------


def _prop_client_identity(seed):
    """Twin feature-dense fleets through ``backend="numpy"`` and
    ``backend="jax"``: identical WRR floats/miss sets, identical run sets
    (content, order, applied state, slice stamps), identical work needs."""
    now = 500.0
    allow_inf = bool(seed % 2)
    A = make_clients(25, seed, allow_inf=allow_inf)
    B = make_clients(25, seed, allow_inf=allow_inf)
    eng_np = BatchClientEngine()
    eng_jx = BatchClientEngine(backend="jax")

    for sa, sb, c in zip(eng_np.wrr_batch(A, now), eng_jx.wrr_batch(B, now), A):
        _assert_wrr_equal(sa, sb, c.host_id)

    runs_a = eng_np.schedule_batch(A, now)
    runs_b = eng_jx.schedule_batch(B, now)
    sig = lambda js: [  # noqa: E731
        (j.instance_id, j.state, j.slice_start, j.deadline_miss) for j in js
    ]
    for ca, cb, ra, rb in zip(A, B, runs_a, runs_b):
        assert sig(ra) == sig(rb), ca.host_id
        assert sig(ca.jobs) == sig(cb.jobs), ca.host_id
        assert sig(ca.running) == sig(cb.running), ca.host_id

    for na, nb in zip(
        eng_np.needs_work_batch(A, now), eng_jx.needs_work_batch(B, now)
    ):
        assert na == nb


@pytest.mark.parametrize("seed", range(6))
def test_client_engine_backend_identity(seed):
    _prop_client_identity(seed)


test_client_engine_hypothesis = hyp(_prop_client_identity, max_examples=6)


# ---------------------------------------------------------------------------
# Pallas quorum_compare digest buckets vs the reference kernel
# ---------------------------------------------------------------------------


def _partition(codes):
    """Label-free view of a grouping: sorted tuple-of-tuples of indices."""
    groups = {}
    for i, c in enumerate(codes):
        groups.setdefault(int(c), []).append(i)
    return sorted(tuple(v) for v in groups.values())


def _ref_group_codes(mat, rtol, atol):
    """The same greedy first-match grouping as ``quorum_group_codes`` but
    with the pure-jnp reference kernel as the pair predicate."""
    n = mat.shape[0]
    codes = np.zeros(n, dtype=np.int64)
    reps = []
    nan_rows = np.isnan(mat).any(axis=1)
    for i in range(n):
        if nan_rows[i]:
            codes[i] = -(i + 1)  # unique stand-in sentinel
            continue
        for g, r in enumerate(reps):
            n_bad, _ = quorum_compare_ref(
                jax.numpy.asarray(mat[i]), jax.numpy.asarray(mat[r]),
                rtol=rtol, atol=atol,
            )
            if int(n_bad) == 0:
                codes[i] = g
                break
        else:
            reps.append(i)
            codes[i] = len(reps) - 1
    return codes


_TOL_BANDS = [(1e-5, 1e-8), (1e-6, 1e-9), (1e-4, 1e-6)]


def _prop_digest_buckets(seed):
    """Pallas-kernel grouping == reference-kernel grouping across tolerance
    bands under the far-from-boundary digest contract; NaN rows are unique
    singletons in both; -0.0 buckets with +0.0."""
    rs = np.random.RandomState(seed)
    d = int(rs.randint(4, 49))
    n_groups = int(rs.randint(1, 4))
    rtol, atol = _TOL_BANDS[int(rs.randint(0, len(_TOL_BANDS)))]
    rows = []
    for g in range(n_groups):
        base = rs.standard_normal(d) * 10.0
        if rs.rand() < 0.5:
            base[rs.rand(d) < 0.3] = 0.0  # exact zeros for the -0.0 corner
        # far-outside-tolerance separation between groups (digest contract)
        base = base + g * (1000.0 * (atol + rtol * 20.0) + 5.0)
        for _ in range(int(rs.randint(1, 4))):
            row = base.copy()
            if rs.rand() < 0.5:
                row[row == 0.0] = -0.0  # must still bucket with +0.0
            rows.append(row)
    if rs.rand() < 0.5:
        bad = rs.standard_normal(d)
        bad[int(rs.randint(0, d))] = np.nan  # NaN rows: always singletons
        rows.append(bad)
    mat = np.stack(rows)[rs.permutation(len(rows))].astype(np.float64)

    got = _partition(quorum_group_codes(mat, rtol, atol))
    want = _partition(_ref_group_codes(mat, rtol, atol))
    assert got == want


@pytest.mark.parametrize("seed", range(12))
def test_quorum_digest_buckets_match_ref(seed):
    _prop_digest_buckets(seed)


test_quorum_digest_hypothesis = hyp(_prop_digest_buckets, max_examples=25)


def test_quorum_digest_negative_zero_and_nan_exact():
    """Deterministic pin of the PR 4 corners: a -0.0 replica groups with
    its +0.0 twin; every NaN-carrying replica is its own group."""
    a = np.array([0.0, 1.0, 2.0, 3.0])
    b = a.copy()
    b[0] = -0.0
    c = a + 100.0
    nan1 = a.copy()
    nan1[2] = np.nan
    nan2 = nan1.copy()
    mat = np.stack([a, b, c, nan1, nan2])
    codes = quorum_group_codes(mat, 1e-5, 1e-8)
    assert codes[0] == codes[1]
    assert codes[2] != codes[0]
    assert len({int(x) for x in codes}) == 4  # {a,b}, {c}, {nan1}, {nan2}
    assert codes[3] != codes[4]


def test_fuzzy_digest_jax_groups_each_job_apart(monkeypatch):
    """With job offsets the kernel route compares rows of one job only: two
    jobs of one payload each found their own group 0, and each job's codes
    partition it as ``quorum_group_codes`` does alone."""
    rs = np.random.RandomState(2)
    x, y = rs.standard_normal(40), rs.standard_normal(40)
    outputs = [x, x, y, y, x, x + 50.0, y]
    off = [0, 2, 4, 7]
    seen = []

    def base(outs):
        raise AssertionError("tensor rows never reach the scalar digest")

    fn = fuzzy_digest_jax(base, 1e-5, 1e-8)
    assert fn.pairwise
    monkeypatch.setattr(jax_backend, "quorum_group_codes",
                        lambda m, r, a: seen.append(len(m)) or quorum_group_codes(m, r, a))
    codes = fn(outputs, job_off=off)
    assert seen == [2, 2, 3]
    assert codes.tolist() == [0, 0, 0, 0, 0, 1, 2]
    whole = fn(outputs)  # no offsets: one job
    assert _partition(whole) == [(0, 1, 4), (2, 3, 6), (5,)]


def test_fuzzy_digest_jax_sends_other_payloads_to_the_base_hook_once():
    calls = []

    def base(outs):
        calls.append(list(outs))
        return np.arange(len(outs), dtype=np.int64) + 100

    fn = fuzzy_digest_jax(base, 1e-5, 1e-8)
    floats = [1.0, 1.0, 2.0, 2.0]
    assert fn(floats, job_off=[0, 2, 4]).tolist() == [100, 101, 102, 103]
    assert calls == [floats]
    # a job of mixed shapes goes to the base hook; the tensor jobs do not
    a, b = np.ones(8), np.ones(9)
    codes = fn([a, a, a, b, a, a], job_off=[0, 2, 4, 6])
    assert calls[1] == [a, b]
    assert codes.tolist() == [0, 0, 100, 101, 0, 0]


class _NoHostScan:
    """``numpy`` for ``jax_backend``, less the host stack and NaN scan."""

    def __getattr__(self, name):
        if name in ("stack", "isnan"):
            raise AssertionError(f"np.{name} ran on the kernel route")
        return getattr(np, name)


def test_fuzzy_digest_jax_puts_each_result_on_the_device_once(monkeypatch):
    """Jobs of 2, 3 (corrupt, honest, honest) and 2 rows: each payload goes
    through ``jax.device_put`` exactly once, as itself, and the route makes
    no host copy or scan of a job's payloads."""
    from repro.core import validator

    rng = np.random.default_rng(8)
    a, b, c = (rng.standard_normal(600).astype(np.float32) for _ in range(3))
    bad = b.copy()
    bad[599] += 2.0
    outputs = [a, a.copy(), bad, b, b.copy(), c, c.copy()]
    off = [0, 2, 5, 7]
    want = np.concatenate([_ref_group_codes(np.stack(outputs[i:j]), 1e-4, 1e-6)
                           for i, j in zip(off[:-1], off[1:])])
    put = []
    real_put = jax.device_put

    def counting_put(x, *args, **kw):
        put.append(x)
        return real_put(x, *args, **kw)

    def no_stack(outs):
        raise AssertionError("the kernel route stacked a job on the host")

    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(validator, "_homogeneous_arrays", no_stack)
    monkeypatch.setattr(jax_backend, "np", _NoHostScan())
    codes = fuzzy_digest_jax(lambda outs: 1 / 0, 1e-4, 1e-6)(outputs, job_off=off)
    assert codes.tolist() == want.tolist() == [0, 0, 0, 1, 1, 0, 0]
    assert len(put) == 7
    assert all(p is o for p, o in zip(put, outputs))


_D = 3 * 256 + 72  # three full 256-lane rows and a tail of 72 elements


def _nan_scan_case(case):
    """(matrix, NaN rows) of one placement of a NaN or an inf."""
    rs = np.random.RandomState(11)
    x = rs.standard_normal(_D).astype(np.float32)
    far = x + np.float32(50.0)
    if case == "row0_nan":
        r0 = x.copy()
        r0[_D // 2] = np.nan
        return np.stack([r0, x, x, far]), [0]
    if case == "tail_nan":
        r1 = x.copy()
        r1[3 * 256 + 10] = np.nan
        return np.stack([x, r1, x]), [1]
    if case == "last_element_nan":
        r2 = x.copy()
        r2[-1] = np.nan
        return np.stack([x, far, r2, far]), [2]
    if case == "inf_row":
        inf = x.copy()
        inf[7] = np.inf
        return np.stack([x, inf, inf.copy(), x, far]), []
    if case == "float64_host":
        r = x.astype(np.float64)
        r3 = r.copy()
        r3[100] = np.nan
        return np.stack([r, r + 50.0, r, r3]), [3]
    raise KeyError(case)


@pytest.mark.parametrize("case", ["row0_nan", "tail_nan", "last_element_nan",
                                  "inf_row", "float64_host"])
def test_the_chip_flags_nan_rows_as_the_reference_grouping_does(case):
    """The device NaN scan, as a host matrix and as the route stages it:
    NaN rows are unique singletons and never representatives; the other
    rows (an ``inf`` row among them) get the reference's codes."""
    mat, nan_rows = _nan_scan_case(case)
    want = _ref_group_codes(mat, 1e-4, 1e-6)
    route = fuzzy_digest_jax(lambda outs: 1 / 0, 1e-4, 1e-6)
    for got in (quorum_group_codes(mat, 1e-4, 1e-6), route(list(mat))):
        assert _partition(got) == _partition(want)
        finite = [i for i in range(len(mat)) if i not in nan_rows]
        assert got[finite].tolist() == want[finite].tolist()
        assert len({int(got[i]) for i in nan_rows} - set(got[finite].tolist())) == len(nan_rows)


# ---------------------------------------------------------------------------
# world device mirror: dirty-upload regression
# ---------------------------------------------------------------------------


def _mk_world(backend, n_hosts=6, seed=11):
    rng = random.Random(seed)
    world = HostArrays(backend=backend)
    for h in range(n_hosts):
        client = Client(
            host_id=h + 1,
            resources={CPU: ClientResource(CPU, 4, 1e10)},
            prefs=ClientPrefs(),
        )
        client.attach(ProjectAttachment(name="p"))
        world.add_host(h + 1, client, 4)
        for k in range(rng.randrange(1, 5)):
            cj = ClientJob(
                instance_id=h * 100 + k,
                job_id=h * 100 + k,
                project="p",
                app_name="w",
                usage={CPU: rng.choice([0.5, 1.0, 2.0])},
                est_flops=1e10,
                est_flop_count=1e13,
                deadline=1e9,
                state=rng.choice([RunState.RUNNING, RunState.PREEMPTED]),
            )
            client.jobs.append(cj)
            world.add_job(h + 1, cj, actual_total=rng.uniform(40.0, 200.0))
        world.sync_run_state(h + 1)
    return world


def _assert_mirror_matches_host(world):
    """After a sync flush, every device column must equal its host column —
    i.e. the incremental dirty-range upload equals a from-scratch upload."""
    m = world._mirror
    m.sync(world)
    assert not m.dirty and not m.all_dirty
    for name in ("q_total", "q_runtime", "q_frac", "q_running", "q_weight", "busy"):
        dev = np.asarray(getattr(m, name))
        host = getattr(world, name)
        assert np.array_equal(dev, host), name
    assert np.array_equal(np.asarray(m.q_cpu), world.q_usage[CPU])


def test_dirty_upload_after_each_mutation_kind():
    """Drive every ``_touch`` writer between device ticks; the device
    columns must match the host arrays after each pass."""
    world = _mk_world("jax")
    ids = list(world.index)
    world.advance_batch(ids, 30.0)
    _assert_mirror_matches_host(world)

    # set_accrued + sync_run_state
    world.set_accrued(1, 0, 7.25)
    for j in world.clients[world.index[2]].jobs:
        j.state = RunState.RUNNING
    world.sync_run_state(2)
    world.advance_batch(ids, 60.0)
    _assert_mirror_matches_host(world)

    # dirty-host refresh: mutate objects out-of-band, then resync
    c3 = world.clients[world.index[3]]
    if c3.jobs:
        c3.jobs[0].state = RunState.DONE
    world.mark_dirty(3)
    world.resync_host(3)
    _assert_mirror_matches_host(world)

    # churn: remove a host, add a job elsewhere
    world.remove_host(4)
    extra = ClientJob(
        instance_id=9999, job_id=9999, project="p", app_name="w",
        usage={CPU: 1.0}, est_flops=1e10, est_flop_count=1e13,
        deadline=1e9, state=RunState.RUNNING,
    )
    world.clients[world.index[5]].jobs.append(extra)
    world.add_job(5, extra, actual_total=55.0)
    world.sync_run_state(5)
    world.advance_batch([h for h in ids if h != 4], 95.0)
    _assert_mirror_matches_host(world)

    # completion path reads through the same mirror
    done = world.completed_rows_batch([h for h in ids if h != 4])
    for h, rows in done.items():
        i = world.index[h]
        cnt = int(world.q_count[i])
        want = np.flatnonzero(
            world.q_running[:cnt, i]
            & (world.q_runtime[:cnt, i] >= world.q_total[:cnt, i] - 1e-6)
        )
        assert np.array_equal(rows, want), h
    _assert_mirror_matches_host(world)


def test_queue_growth_forces_full_reupload():
    """Growing the queue matrix reallocates host storage; the mirror's
    shape check must catch it and re-upload everything."""
    world = _mk_world("jax", n_hosts=2)
    world.advance_batch([1, 2], 10.0)
    q_before = world.q_total.shape
    c = world.clients[world.index[1]]
    for k in range(world._q + 1):  # force at least one _grow_queue
        cj = ClientJob(
            instance_id=5000 + k, job_id=5000 + k, project="p", app_name="w",
            usage={CPU: 0.5}, est_flops=1e10, est_flop_count=1e13,
            deadline=1e9, state=RunState.PREEMPTED,
        )
        c.jobs.append(cj)
        world.add_job(1, cj, actual_total=80.0)
    assert world.q_total.shape != q_before
    world.advance_batch([1, 2], 40.0)
    _assert_mirror_matches_host(world)


def test_world_backend_twin_parity():
    """A NumPy twin driven through the identical mutation/tick sequence
    stays bitwise identical in accrual state and REC debits."""

    def drive(backend):
        world = _mk_world(backend, seed=23)
        ids = list(world.index)
        for t in (15.0, 47.5, 160.0, 500.0):
            world.advance_batch(ids, t)
            if t == 47.5:
                # host 2's first job has instance id 100 (h=1, k=0)
                if 100 in world.row_of[world.index[2]]:
                    world.set_accrued(2, 100, 3.5)
                world.remove_host(6)
                ids = [h for h in ids if h != 6]
            if t == 160.0:
                done = world.completed_rows_batch(ids)
                for h, rows in done.items():
                    if len(rows):
                        world.remove_rows(h, rows)
        return world

    wn = drive("numpy")
    wj = drive("jax")
    assert np.array_equal(wn.q_runtime, wj.q_runtime)
    assert np.array_equal(wn.q_frac, wj.q_frac)
    assert np.array_equal(wn.busy, wj.busy)
    assert np.array_equal(wn.q_count, wj.q_count)
    for cn, cj in zip(wn.clients, wj.clients):
        if cn is None or cj is None:
            assert cn is None and cj is None
            continue
        recs_n = {k: (a.balance, a.total_used) for k, a in cn.rec.accounts.items()}
        recs_j = {k: (a.balance, a.total_used) for k, a in cj.rec.accounts.items()}
        assert recs_n == recs_j
