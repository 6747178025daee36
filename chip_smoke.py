"""Bring-up run of the system's JAX device path on one TPU chip.

    python chip_smoke.py

Drives the main path once through the entry points a user calls, with
``backend="jax"`` / ``engine_backend="jax"``, at the paper's §1.1 sizes,
and checks each phase against the NumPy engines in the same process:

1. world tick: a 700,000-host columnar world (queue depth 4), three
   accrual ticks and one completion mask, against a NumPy twin world;
2. emulator: ``GridSimulation`` with churn and 60% availability at
   100,000 hosts over DAY/64, against the same seed on NumPy;
3. served path: a 4-shard ``ProjectServer`` behind ``SchedulerService``
   with coalescing, 2,048 WORK requests through ``run_load``, then one
   fixed ``rpc_batch`` against a NumPy server built from the same seed;
4. validation: a transitioner validate pass over 2^22-element float
   replicas through the compiled ``quorum_compare`` Pallas kernel, against
   the NumPy digest engine.

Decision fields (masks, counts, states, assignments, verdicts) must match
exactly; float fields are reported as a count of differing elements and
the largest relative difference. Each phase prints one line; the wall
times are a first chip reading, not a benchmark. The last line is a JSON
object naming the device. With no TPU the script exits non-zero before
any phase runs; it never falls back to the CPU.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
import warnings
from pathlib import Path
from typing import Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.core import (  # noqa: E402
    App,
    AppVersion,
    GridSimulation,
    Host,
    InstanceOutcome,
    InstanceState,
    Job,
    Platform,
    ProcessingResource,
    ProjectServer,
    ResourceType,
    default_cpu_plan_class,
    fuzzy_comparator,
    make_population,
    next_id,
    reset_ids,
)
from repro.core import jax_backend  # noqa: E402
from repro.core.scenarios import _first_divergence  # noqa: E402
from repro.core.scheduler import ResourceRequest, ScheduleRequest  # noqa: E402
from repro.core.tracing import CompileCounter  # noqa: E402
from repro.core.world import HostArrays  # noqa: E402
from repro.kernels.quorum_compare.ops import quorum_compare  # noqa: E402
from repro.service import SchedulerService, run_load  # noqa: E402

CPU = ResourceType.CPU
DAY = 86400.0
_OSES = ("windows", "mac", "linux")


class PhaseFailed(AssertionError):
    """A decision field differed from the NumPy engines'."""


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------


def float_diff(a: np.ndarray, b: np.ndarray) -> Tuple[int, float]:
    """(elements that differ, largest relative difference) of two float
    arrays; equal infinities and NaNs count as equal."""
    a = np.asarray(a, dtype=np.float64).ravel()
    b = np.asarray(b, dtype=np.float64).ravel()
    diff = ~((a == b) | (np.isnan(a) & np.isnan(b)))
    if not diff.any():
        return 0, 0.0
    da, db = a[diff], b[diff]
    scale = np.maximum(np.abs(da), np.abs(db))
    rel = np.where(scale > 0, np.abs(da - db) / np.where(scale > 0, scale, 1.0), np.inf)
    return int(diff.sum()), float(rel.max())


def report(phase: str, sizes: Dict, wall_s: float, counts: Tuple[int, int],
           result: Dict) -> str:
    line = (
        f"[{phase}] sizes={json.dumps(sizes)} "
        f"wall_s={wall_s!r} (first chip reading, not a benchmark) "
        f"compiles={counts[0]} cache_hits={counts[1]} "
        f"result={json.dumps(result)}"
    )
    print(line, flush=True)
    return line


class _Phase:
    """Times a phase and counts its compilations."""

    def __enter__(self):
        self.counter = CompileCounter.get()
        self.c0 = self.counter.snapshot()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        c1 = self.counter.snapshot()
        self.counts = (c1[0] - self.c0[0], c1[1] - self.c0[1])
        return False


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------------
# phase 1: world accrual + completion at the paper's fleet
# ---------------------------------------------------------------------------


def build_world(backend: str, n_hosts: int, depth: int, seed: int = 3) -> HostArrays:
    """Columnar world filled column-wise (no per-host objects), so a
    700k-host world builds in seconds; clients stay ``None``."""
    rs = np.random.RandomState(seed)
    world = HostArrays(backend=backend)
    world._grow_hosts(n_hosts)
    world._grow_queue(depth)
    world.n = n_hosts
    world.ids[:n_hosts] = np.arange(1, n_hosts + 1)
    world.index = {h + 1: h for h in range(n_hosts)}
    world.alive[:n_hosts] = True
    world.available[:n_hosts] = rs.rand(n_hosts) < 0.95
    world.clients = [None] * n_hosts
    world.queue_jobs = [[] for _ in range(n_hosts)]
    world.row_of = [{} for _ in range(n_hosts)]
    world.project = [None] * n_hosts
    world.multi = [False] * n_hosts
    counts = rs.randint(1, depth + 1, n_hosts)
    world.q_count[:n_hosts] = counts
    Q = world._q
    rowmask = np.arange(Q)[:, None] < counts[None, :]
    tot = np.where(rowmask, rs.uniform(3600.0, 7 * DAY, (Q, n_hosts)), 0.0)
    run = np.where(rowmask, tot * rs.rand(Q, n_hosts) * 0.5, 0.0)
    world.q_total[:, :n_hosts] = tot
    world.q_runtime[:, :n_hosts] = run
    world.q_frac[:, :n_hosts] = np.where(rowmask, run / np.maximum(tot, 1e-9), 0.0)
    world.q_running[:, :n_hosts] = rowmask & (rs.rand(Q, n_hosts) < 0.7)
    world.q_weight[:, :n_hosts] = np.where(rowmask, 1.0, 0.0)
    world.q_usage[CPU][:, :n_hosts] = np.where(
        rowmask, rs.choice([0.5, 1.0, 2.0], (Q, n_hosts)), 0.0
    )
    return world


def phase_world_tick(n_hosts: int, depth: int, ticks: int) -> Dict:
    """``ticks`` accrual passes through ``_advance_cols`` plus one
    completion mask, on a jax world and its NumPy twin. Ticks run up to a
    day, so jobs hit the clamp and complete."""
    wn = build_world("numpy", n_hosts, depth)
    wj = build_world("jax", n_hosts, depth)
    debit_n: List[np.ndarray] = []
    debit_j: List[np.ndarray] = []
    with warnings.catch_warnings(record=True) as caught, _Phase() as ph:
        warnings.simplefilter("always")
        for tick in range(ticks):
            rs = np.random.RandomState(5 + tick)
            sub = np.flatnonzero(wn.available[:n_hosts] & (rs.rand(n_hosts) < 0.9))
            dts = rs.uniform(60.0, DAY, len(sub))
            dn, tn = wn._advance_cols(sub, dts)
            dj, tj = wj._advance_cols(sub, dts)
            _require(np.array_equal(tn, tj), f"tick {tick}: touched masks differ")
            debit_n.append(dn)
            debit_j.append(dj)
        idx = np.arange(n_hosts)
        cn = wn.completed_mask(idx)
        cj = wj.completed_mask(idx)
        jax.block_until_ready(wj._mirror.q_runtime)
    donation = sum("donated buffers" in str(w.message) for w in caught)
    _require(np.array_equal(cn, cj), "completion masks differ")
    result: Dict = {
        "touched_masks": "identical",
        "completion_mask": "identical",
        "completed_rows": int(cn.sum()),
        "donation_warnings": donation,
    }
    for name in ("q_runtime", "q_frac", "busy"):
        n, rel = float_diff(getattr(wn, name), getattr(wj, name))
        result[name] = {"n_diff": n, "max_rel": rel}
    n, rel = float_diff(np.concatenate(debit_n), np.concatenate(debit_j))
    result["debits"] = {"n_diff": n, "max_rel": rel}
    report("phase 1 world_tick",
           {"n_hosts": n_hosts, "depth": depth, "ticks": ticks},
           ph.wall, ph.counts, result)
    return result


# ---------------------------------------------------------------------------
# phase 2: the emulator end to end
# ---------------------------------------------------------------------------


def _work_app(name: str, min_quorum: int) -> App:
    app = App(
        name=name,
        min_quorum=min_quorum,
        init_ninstances=min_quorum,
        delay_bound=4 * 3600.0,
        comparator=fuzzy_comparator(rtol=1e-6, atol=1e-9),
    )
    for osn in _OSES:
        app.add_version(
            AppVersion(
                id=next_id("appver"),
                app_name=name,
                platform=Platform(osn, "x86_64"),
                version_num=1,
                plan_class=default_cpu_plan_class(),
            )
        )
    return app


def _run_emulator(backend: str, n_hosts: int, horizon: float):
    """A churn+availability emulation: 60% availability, churn of one
    host per two days, eight 0.1-hour jobs a host, 60 s epochs."""
    reset_ids()
    server = ProjectServer(name="p", purge_delay=1e18, engine_backend=backend)
    server.add_app(_work_app("w", 2))
    pop = make_population(
        n_hosts, seed=1, availability=0.6, churn_rate=1.0 / (2 * DAY), horizon=horizon
    )
    sim = GridSimulation(server, pop, seed=3, epoch=60.0)
    for _ in range(n_hosts * 8):
        server.submit_job(
            Job(id=next_id("job"), app_name="w", est_flop_count=0.1 * 3600 * 16.5e9),
            0.0,
        )
    metrics = sim.run(horizon)
    return server, metrics


def _split_metrics(m) -> Tuple[Dict, Dict]:
    fields = vars(m)
    ints = {k: v for k, v in fields.items() if isinstance(v, int)}
    floats = {k: v for k, v in fields.items() if not isinstance(v, int)}
    return ints, floats


def phase_emulator(n_hosts: int, horizon: float) -> Dict:
    with _Phase() as ph:
        sj, mj = _run_emulator("jax", n_hosts, horizon)
    sn, mn = _run_emulator("numpy", n_hosts, horizon)
    ints_j, floats_j = _split_metrics(mj)
    ints_n, floats_n = _split_metrics(mn)
    inst_j, inst_n = sj.store.instances, sn.store.instances
    decisions = (
        ("SimMetrics counts", ints_n, ints_j),
        ("server counts", sn.counts(), sj.counts()),
        ("job states", {j: x.state for j, x in sn.store.jobs.items()},
         {j: x.state for j, x in sj.store.jobs.items()}),
        ("validate states", {i: x.validate_state for i, x in inst_n.items()},
         {i: x.validate_state for i, x in inst_j.items()}),
    )
    for what, a, b in decisions:
        d = _first_divergence(a, b)
        if d is not None:
            print(f"[phase 2 emulator] {what} diverged first at {d}", flush=True)
        _require(d is None, f"{what}: {d}")
    result: Dict = {
        "instances_executed": ints_j["instances_executed"],
        "completed_instances": ints_j["completed_instances"],
        "jobs_success": sj.counts().get("jobs_success"),
        "rpcs": ints_j["rpcs"],
        "decisions": "identical",
    }
    for k in sorted(floats_n):
        n, rel = float_diff(floats_n[k], floats_j[k])
        result[k] = {"n_diff": n, "max_rel": rel}
    keys = sorted(sn.credit.total)
    _require(keys == sorted(sj.credit.total), "credit keys differ")
    n, rel = float_diff([sn.credit.total[k] for k in keys],
                        [sj.credit.total.get(k, np.nan) for k in keys])
    result["credit_totals"] = {"n": len(keys), "n_diff": n, "max_rel": rel}
    ids = sorted(inst_n)
    for name in ("granted_credit", "runtime"):
        n, rel = float_diff([getattr(inst_n[i], name) for i in ids],
                            [getattr(inst_j[i], name) for i in ids])
        result[f"instance_{name}"] = {"n": len(ids), "n_diff": n, "max_rel": rel}
    report("phase 2 emulator", {"n_hosts": n_hosts, "horizon": horizon},
           ph.wall, ph.counts, result)
    return result


# ---------------------------------------------------------------------------
# phase 3: the served path
# ---------------------------------------------------------------------------


def _make_served(backend: str, n_hosts: int, n_jobs: int, cache_size: int,
                 n_shards: int) -> ProjectServer:
    """A served project: one min_quorum=1 app, a pre-filled cache, so
    every RPC is a live dispatch attempt."""
    reset_ids()
    server = ProjectServer(
        name="served",
        purge_delay=1e18,
        cache_size=cache_size,
        n_scheduler_instances=n_shards,
        vector_dispatch=True,
        engine_backend=backend,
    )
    app = App(name="work", min_quorum=1, init_ninstances=1)
    for osn in _OSES:
        app.add_version(
            AppVersion(
                id=next_id("appver"),
                app_name="work",
                platform=Platform(osn, "x86_64"),
                version_num=1,
                plan_class=default_cpu_plan_class(),
            )
        )
    server.add_app(app)
    for _ in range(n_jobs):
        server.submit_job(Job(id=next_id("job"), app_name="work", est_flop_count=1e12), 0.0)
    for i in range(n_hosts):
        server.add_host(
            Host(
                id=i + 1,
                platforms=(Platform(_OSES[i % 3], "x86_64"),),
                resources={CPU: ProcessingResource(CPU, 8, 2e10)},
                volunteer_id=i + 1,
            )
        )
    server.tick(0.0)
    return server


async def _serve(server: ProjectServer, n_requests: int):
    svc = SchedulerService(server, coalesce=True, max_batch=1024)
    await svc.start()
    try:
        rep = await run_load("127.0.0.1", svc.port, n_clients=n_requests, n_conns=64)
    finally:
        await svc.stop()
    return rep, svc.stats()


def _assignments(replies) -> List[List[Tuple[int, int, int]]]:
    return [[(d.job.id, d.instance.id, d.version.id) for d in r.jobs] for r in replies]


def phase_served(n_hosts: int, n_jobs: int, cache_size: int, n_shards: int,
                 n_requests: int, batch: int) -> Dict:
    server = _make_served("jax", n_hosts, n_jobs, cache_size, n_shards)
    with _Phase() as ph:
        load, stats = asyncio.run(_serve(server, n_requests))
    _require(load.errors == 0 and stats["errors"] == 0,
             f"errors: load={load.errors} service={stats['errors']}")
    _require(load.replies == n_requests, f"{load.replies} replies to {n_requests}")
    server.store.check_invariants()

    def fixed_batch(backend: str):
        srv = _make_served(backend, n_hosts, n_jobs, cache_size, n_shards)
        reqs = [
            ScheduleRequest(host_id=h, requests={CPU: ResourceRequest(req_runtime=4 * 3600.0)})
            for h in range(1, batch + 1)
        ]
        return srv.rpc_batch(reqs, 0.0)

    rj, rn = fixed_batch("jax"), fixed_batch("numpy")
    aj, an = _assignments(rj), _assignments(rn)
    _require(aj == an, "rpc_batch assignments differ")
    n, rel = float_diff([d.est_runtime for r in rn for d in r.jobs],
                        [d.est_runtime for r in rj for d in r.jobs])
    result = {
        "replies": load.replies,
        "errors": load.errors + stats["errors"],
        "jobs_received": load.jobs_received,
        "waves": stats["waves"],
        "max_wave": stats["max_wave"],
        "rpcs_per_s": load.rpcs_per_s,
        "p99_ms": load.p99_ms,
        "invariants": "ok",
        "batch_assignments": "identical",
        "batch_jobs": sum(len(a) for a in aj),
        "est_runtime": {"n_diff": n, "max_rel": rel},
    }
    report("phase 3 served",
           {"n_hosts": n_hosts, "n_jobs": n_jobs, "cache_size": cache_size,
            "n_shards": n_shards, "n_requests": n_requests, "batch": batch},
           ph.wall, ph.counts, result)
    return result


# ---------------------------------------------------------------------------
# phase 4: validation digest through the compiled kernel
# ---------------------------------------------------------------------------

_RTOL, _ATOL = 1e-6, 1e-9


def _validation_server(backend: str, n_jobs: int, replicas: int, payload: int,
                       seed: int = 11) -> ProjectServer:
    """Jobs whose replicas all reported float32 tensors of ``payload``
    elements; a seeded third of the replicas are corrupted far outside
    the comparator's tolerance."""
    reset_ids()
    rs = np.random.RandomState(seed)
    server = ProjectServer(name="v", purge_delay=1e18, engine_backend=backend)
    app = server.add_app(_work_app("w", 2))
    vid = app.versions[-1].id  # the linux version
    for h in range(replicas):
        server.add_host(
            Host(
                id=h + 1,
                platforms=(Platform("linux", "x86_64"),),
                resources={CPU: ProcessingResource(CPU, 4, 16.5e9)},
                volunteer_id=h + 1,
            )
        )
    store = server.store
    for _ in range(n_jobs):
        job = server.submit_job(
            Job(id=next_id("job"), app_name="w", est_flop_count=0.2 * 3600 * 16.5e9,
                max_success_instances=replicas + 2)
        )
        truth = rs.standard_normal(payload).astype(np.float32)
        for k in range(replicas):
            inst = store.create_instance(job)
            inst.host_id = k + 1
            inst.app_version_id = vid
            inst.state = InstanceState.IN_PROGRESS
            inst.state = InstanceState.OVER
            inst.outcome = InstanceOutcome.SUCCESS
            inst.runtime = 700.0 + k
            inst.peak_flop_count = inst.runtime * 16.5e9
            if rs.rand() < 1 / 3:
                inst.output = truth + rs.uniform(1.0, 2.0, payload).astype(np.float32)
            else:
                inst.output = truth.copy()
    return server


def _partitions(server: ProjectServer) -> Dict[int, List[int]]:
    """Per job, the grouping of its replicas under the digest hook the
    server's validate pass used, as first-occurrence labels."""
    store = server.store
    digest = server.transitioners[0]._engine.digest_fn(store.apps["w"])
    out: Dict[int, List[int]] = {}
    for jid in sorted(store.jobs):
        outs = [
            i.output for i in store.job_instances(jid)
            if i.outcome == InstanceOutcome.SUCCESS
        ]
        codes = digest(outs)
        first: Dict[int, int] = {}
        out[jid] = [first.setdefault(int(c), len(first)) for c in codes]
    return out


def phase_validation(n_jobs: int, replicas: int, payload: int) -> Dict:
    # each server is built and ticked before the next: instance ids come
    # from one process-wide counter, and the tick creates tie-breakers
    sj = _validation_server("jax", n_jobs, replicas, payload)
    with _Phase() as ph:
        sj.transitioners[0].tick(60.0)
    sn = _validation_server("numpy", n_jobs, replicas, payload)
    sn.transitioners[0].tick(60.0)
    verdicts = (
        ("validate states",
         {i: x.validate_state for i, x in sn.store.instances.items()},
         {i: x.validate_state for i, x in sj.store.instances.items()}),
        ("job states / canonicals",
         {j: (x.state, x.canonical_instance_id) for j, x in sn.store.jobs.items()},
         {j: (x.state, x.canonical_instance_id) for j, x in sj.store.jobs.items()}),
    )
    for what, a, b in verdicts:
        d = _first_divergence(a, b)
        if d is not None:
            print(f"[phase 4 validation] {what} diverged first at {d}", flush=True)
        _require(d is None, f"{what}: {d}")
    pj, pn = _partitions(sj), _partitions(sn)
    _require(pj == pn, "replica partitions differ")
    sj.store.check_invariants()
    x = np.zeros(payload, dtype=np.float32)
    hlo = quorum_compare.lower(x, x, rtol=_RTOL, atol=_ATOL).as_text()
    compiled = "tpu_custom_call" in hlo
    if jax.default_backend() == "tpu":
        _require(compiled, "validation kernel was not compiled (no tpu_custom_call)")
    result = {
        "partitions": "identical",
        "verdicts": "identical",
        "jobs_valid": sum(1 for j in sj.store.jobs.values() if j.canonical_instance_id is not None),
        "groups": sum(len(set(p)) for p in pj.values()),
        "kernel": "compiled" if compiled else "interpret",
    }
    report("phase 4 validation",
           {"n_jobs": n_jobs, "replicas": replicas, "payload": payload},
           ph.wall, ph.counts, result)
    return result


# ---------------------------------------------------------------------------


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}",
            file=sys.stderr,
        )
        return 2
    cache_dir = jax_backend.configure_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    phase_world_tick(n_hosts=700_000, depth=4, ticks=3)
    phase_emulator(n_hosts=100_000, horizon=DAY / 64)
    phase_served(n_hosts=2048, n_jobs=20_000, cache_size=1024, n_shards=4,
                 n_requests=2048, batch=256)
    phase_validation(n_jobs=8, replicas=3, payload=1 << 22)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    counter = CompileCounter.get()
    print(f"total compiles={counter.compiles} cache_hits={counter.cache_hits}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
