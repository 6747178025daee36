"""Driver of served traffic: scheduler RPCs offered to the TCP service.

One run builds the project from the seed (the fleet registered, the job
backlog submitted and the cache filled), warms every shape the dispatch
jits can take, starts ``SchedulerService`` in this process (which holds the
chip) and the open-loop generator in another (which never touches it), and
measures the window. The service's calls into the project are recorded in
order, so that the plain reference can replay them afterwards and every
reply on the wire can be compared with what the policy gives.
"""
from __future__ import annotations

import asyncio
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from perfbench.harness import device, trace as tracing
from perfbench.harness.cell import BENCH_DIR, Cell, Run
from perfbench.harness.counters import (CompileCounter, GcLog, Spans, earlier_line,
                                        float_diff, percentile)
from perfbench.reference.dispatch import DispatchReference

GENERATOR = BENCH_DIR / "loadgen" / "openloop.py"


# ---------------------------------------------------------------------------
# the deployment, made from the seed
# ---------------------------------------------------------------------------


def fleet(cfg: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(OS index, CPU peak FLOPS) per host, host id = index + 1.

    The OS shares are exact counts and the speeds a fixed lognormal set
    (mean ``cpu_gflops_mean``, log-spread ``speed_spread``); the seed only
    decides which host gets which."""
    n = int(cfg["hosts"])
    rng = np.random.default_rng([int(seed), 1])
    shares = [float(s) for _, s in cfg["os_split"]]
    counts = [int(round(s * n)) for s in shares[:-1]]
    counts.append(n - sum(counts))
    os_index = rng.permutation(np.repeat(np.arange(len(counts), dtype=np.int64), counts))
    sigma = float(cfg["speed_spread"])
    z = np.random.default_rng(0).standard_normal(n)
    speed = float(cfg["cpu_gflops_mean"]) * 1e9 * np.exp(sigma * rng.permutation(z) - sigma**2 / 2)
    return os_index, speed


def job_flops(cfg: Dict) -> float:
    return float(cfg["job_hours_on_mean_host"]) * 3600.0 * float(cfg["cpu_gflops_mean"]) * 1e9


def build_project(cfg: Dict, os_index: np.ndarray, speed: np.ndarray):
    from repro.core import (App, AppVersion, Host, Job, Platform, ProcessingResource,
                            ProjectServer, ResourceType, default_cpu_plan_class,
                            next_id, reset_ids)

    cpu = ResourceType.CPU
    reset_ids()
    server = ProjectServer(
        name=cfg["name"],
        purge_delay=1e18,
        cache_size=int(cfg["cache_slots"]),
        n_scheduler_instances=int(cfg["scheduler_shards"]),
        vector_dispatch=True,
        engine_backend=cfg["engine_backend"],
    )
    app = App(
        name="work",
        min_quorum=int(cfg["min_quorum"]),
        init_ninstances=int(cfg["init_ninstances"]),
        delay_bound=float(cfg["delay_bound_days"]) * 86400.0,
    )
    oses = [name for name, _ in cfg["os_split"]]
    for osn in oses:
        app.add_version(AppVersion(id=next_id("appver"), app_name="work",
                                   platform=Platform(osn, "x86_64"), version_num=1,
                                   plan_class=default_cpu_plan_class()))
    server.add_app(app)
    flops = job_flops(cfg)
    for _ in range(int(cfg["jobs"])):
        server.submit_job(Job(id=next_id("job"), app_name="work", est_flop_count=flops), 0.0)
    platforms = [(Platform(osn, "x86_64"),) for osn in oses]
    ncpus = int(cfg["ncpus"])
    on_fraction = float(cfg["availability"])
    for i, (o, s) in enumerate(zip(os_index.tolist(), speed.tolist())):
        server.add_host(Host(
            id=i + 1,
            platforms=platforms[o],
            resources={cpu: ProcessingResource(cpu, ncpus, s)},
            on_fraction=on_fraction,
            volunteer_id=i + 1,
        ))
    server.tick(0.0)
    return server


def reference(cfg: Dict, os_index: np.ndarray, speed: np.ndarray,
              dtype=np.float64) -> DispatchReference:
    return DispatchReference(
        os_index, speed,
        n_jobs=int(cfg["jobs"]),
        init_instances=int(cfg["init_ninstances"]),
        job_flops=job_flops(cfg),
        delay_bound=float(cfg["delay_bound_days"]) * 86400.0,
        availability=float(cfg["availability"]),
        cache_size=int(cfg["cache_slots"]),
        n_shards=int(cfg["scheduler_shards"]),
        dtype=dtype,
    )


def warm_dispatch(cache_size: int) -> None:
    """Compile every shape the dispatch jits can take: the eligibility
    pass over the whole cache and each power-of-two candidate bucket of the
    mask and score passes, up to the cache size."""
    from repro.core import jax_backend as jb

    jb.dispatch_elig(np.zeros(cache_size, bool), np.full(cache_size, -1, np.int64), 1, 1)
    m = 8
    while m <= cache_size:
        jb.dispatch_group_mask(np.ones(m, bool), np.full(m, -1, np.int64),
                               np.full(m, -2, np.int64), np.ones(m, bool))
        z = np.zeros(m)
        jb.dispatch_scores(z, z, z, z, np.ones(m), np.ones(m), 0.5, (10.0, 1.0, 1.0, 5.0))
        m *= 2


# ---------------------------------------------------------------------------
# recording what the service asks of the project
# ---------------------------------------------------------------------------


class Recorder:
    """Wraps the project's entry points that the service calls, keeping
    each call in order (for the reference) and timing it as a span, with
    the thread's CPU time in it and whether the cache generation changed
    since the previous call (a feeder fill or a work migration, after which
    each shard rebuilds its dispatch snapshot)."""

    def __init__(self, server, spans: Spans) -> None:
        from repro.core import ResourceType

        cpu = ResourceType.CPU
        self.events: List = []
        self.waves: List[Tuple[float, float, int]] = []  # (t0, t1, requests)
        self.calls: List[Tuple[float, bool]] = []  # (thread CPU s, generation changed)
        batch, rpc, fill = server.rpc_batch, server.rpc, server.feeder.fill
        feeder = server.feeder
        version = {"seen": feeder.version}

        def key(r):
            return (r.host_id, r.requests[cpu].req_runtime)

        def timed(fn, arg, now, n):
            c0, t0 = time.thread_time(), time.perf_counter()
            with spans.span("rpc_batch"):
                out = fn(arg, now)
            self.waves.append((t0, time.perf_counter(), n))
            self.calls.append((time.thread_time() - c0, feeder.version != version["seen"]))
            version["seen"] = feeder.version
            return out

        def rpc_batch(requests, now):
            self.events.append(("wave", [key(r) for r in requests]))
            return timed(batch, requests, now, len(requests))

        def rpc_one(request, now):
            self.events.append(("rpc", key(request)))
            return timed(rpc, request, now, 1)

        def feeder_fill():
            self.events.append(("fill",))
            with spans.span("feeder_fill"):
                return fill()

        server.rpc_batch = rpc_batch
        server.rpc = rpc_one
        server.feeder.fill = feeder_fill

    def slow_calls(self, lo: float, hi: float, slow_s: float, gcs: List) -> Dict:
        """The window's calls of at least ``slow_s``: how many, the longest
        (ms), their thread CPU over wall time, how many followed a change of
        the cache generation, and the collections that overlapped them."""
        slow = [(t0, t1, c, g) for (t0, t1, _), (c, g) in zip(self.waves, self.calls)
                if lo <= t0 < hi and t1 - t0 >= slow_s]
        wall = sum(t1 - t0 for t0, t1, _, _ in slow)
        overlap = [(g, round((b - a) * 1e3, 3)) for g, a, b in gcs
                   if any(a < t1 and b > t0 for t0, t1, _, _ in slow)]
        return {
            "count": len(slow),
            "longest_ms": sorted((round((t1 - t0) * 1e3, 3) for t0, t1, _, _ in slow),
                                 reverse=True)[:10],
            "cpu_share": sum(c for _, _, c, _ in slow) / wall if wall else None,
            "after_generation_change": sum(g for _, _, _, g in slow),
            "gc_overlapping": overlap[:20],
        }


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


async def _serve(server, cell: Cell, seed: int, seconds: float, trace_dir: Optional[str],
                 spans: Spans, counter: CompileCounter) -> Dict:
    from repro.service import SchedulerService

    cfg, tr = cell.config, cell.traffic
    svc_cfg = cfg["service"]
    svc = SchedulerService(server, coalesce=bool(svc_cfg["coalesce"]),
                           max_batch=int(svc_cfg["max_batch"]),
                           refill_every=int(svc_cfg["refill_every"]))
    await svc.start()
    loop = asyncio.get_running_loop()
    marks: Dict[str, Tuple[float, Dict]] = {}
    try:
        t_start = time.perf_counter() + float(tr["start_delay_s"])
        params = {
            "host": "127.0.0.1", "port": svc.port, "t_start": t_start, "seed": seed,
            "rate_per_s": tr["rate_per_s"], "warmup_s": tr["warmup_s"],
            "seconds": seconds, "fleet": cfg["hosts"],
            "req_runtime_h": tr["req_runtime_h"], "connections": tr["connections"],
            "grace_s": tr["grace_s"],
        }
        w0 = t_start + float(tr["warmup_s"])
        w1 = w0 + seconds
        for name, at in (("w0", w0), ("w1", w1)):
            loop.call_later(max(0.0, at - time.perf_counter()),
                            lambda name=name: marks.__setitem__(
                                name, (time.perf_counter(), dict(svc.stats()),
                                       counter.snapshot())))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if trace_dir is not None:
            tracing.start(trace_dir)
            with spans.span("clock"):
                pass
        proc = await asyncio.create_subprocess_exec(
            sys.executable, str(GENERATOR), json.dumps(params),
            stdout=asyncio.subprocess.PIPE, env=env)
        try:
            out, _ = await proc.communicate()
        finally:
            if proc.returncode is None:
                proc.kill()
                await proc.wait()
        trace_path = tracing.stop(trace_dir) if trace_dir is not None else None
        if proc.returncode != 0:
            raise RuntimeError(f"load generator exited with {proc.returncode}")
    finally:
        await svc.stop()
    gen = json.loads(out)
    return {"gen": gen, "w0": w0, "w1": w1, "marks": marks, "trace_path": trace_path}


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices: List,
        t_process_start: float, trace_dir: str) -> Run:
    cfg, tr = cell.config, cell.traffic
    os_index, speed = fleet(cfg, seed)
    t0 = time.perf_counter()
    server = build_project(cfg, os_index, speed)
    t_build = time.perf_counter() - t0
    warm_dispatch(int(cfg["cache_slots"]))
    t_warm = time.perf_counter() - t0 - t_build
    spans = Spans()
    rec = Recorder(server, spans)
    counter = CompileCounter.get()
    with GcLog() as gcs:
        served = asyncio.run(_serve(server, cell, seed, seconds,
                                    trace_dir if trace else None, spans, counter))
    w0, w1 = served["w0"], served["w1"]
    rows = served["gen"]["rows"]
    dev = device.describe(devices)

    # -- end-to-end numbers, from the generator's clock ----------------------
    window = [r for r in rows if r[1] == "window"]
    close = w1 + float(tr["grace_s"])
    lat = [((r[4] if r[7] == "ok" else math.inf) - r[2]) for r in window]
    lat = [min(x, close - r[2]) for x, r in zip(lat, window)]  # a miss waited till close
    replies_in_window = sum(1 for r in rows if r[7] == "ok" and w0 <= r[4] <= w1)
    late = [(r[3] - r[2]) for r in window if r[3] is not None]
    failed = sum(1 for r in window if r[7] != "ok")
    e2e = {
        "rpc_p50_ms": percentile(lat, 0.50) * 1e3,
        "rpc_per_s": replies_in_window / seconds,
        "setup_s": w0 - t_process_start,
    }
    marks = served["marks"]
    earlier_line("served", {
        "build_s": t_build, "warm_s": t_warm, "requests": len(rows),
        "window_requests": len(window), "window_failed": failed,
        "rpc_p99_ms": percentile(lat, 0.99) * 1e3,
        "late_p99_ms": percentile(late, 0.99) * 1e3,
        "waves": len(rec.waves), "max_wave": max((n for _, _, n in rec.waves), default=0),
        "compiles_in_window": marks["w1"][2][0] - marks["w0"][2][0],
        "cache_hits_in_window": marks["w1"][2][1] - marks["w0"][2][1],
    })
    earlier_line("served.stalls", {
        "gc_in_window": gcs.summary(w0, w1),
        "calls_over_50ms": rec.slow_calls(w0, w1, 0.05, gcs.records),
        "generation_changes_in_window": sum(
            g for (t0, _, _), (_, g) in zip(rec.waves, rec.calls) if w0 <= t0 < w1),
    })

    # -- correctness: the replies against the plain reference ---------------
    t_ref = time.perf_counter()
    expect = reference(cfg, os_index, speed).replay(rec.events)
    checks = compare(rows, expect, tr["limits"])
    earlier_line("served.reference", {"seconds": time.perf_counter() - t_ref,
                                      "replayed": len(expect)})

    run_ = Run(setup_s=e2e["setup_s"], end_to_end=e2e, attempted=len(window),
               failed=failed, checks=checks, device=dev, window=(w0, w1))
    run_.data = {"window_rows": window, "waves": rec.waves, "marks": marks,
                 "late_s": late, "latency_s": lat, "spans": spans, "rows": rows, "events": rec.events,
                 "fleet": (os_index, speed)}
    if trace:
        run_.trace = tracing.load(served["trace_path"])
        run_.trace_offset_ns = tracing.clock_offset(run_.trace, spans.records)
    return run_


def compare(rows: List, expect: Dict, limits: Dict) -> Dict[str, Tuple[float, float]]:
    """Every reply on the wire against the reference's reply to the same
    request: assignment lists that differ, requests never answered (or
    answered with an error), and the widest relative gap of a runtime or
    FLOPS estimate."""
    mismatch = unanswered = 0
    got_est: List[float] = []
    want_est: List[float] = []
    for r in rows:
        if r[7] != "ok":
            unanswered += 1
            continue
        want = expect.get((r[5], r[6]))
        got = r[8]
        if want is None or [tuple(j[:3]) for j in got] != [w[:3] for w in want]:
            mismatch += 1
            continue
        for j, w in zip(got, want):
            got_est += [j[3], j[4]]
            want_est += [w[4], w[3]]
    gap = float_diff(got_est, want_est)[1]
    return {
        "reply_mismatch": (float(mismatch), float(limits["reply_mismatch"])),
        "unanswered": (float(unanswered), float(limits["unanswered"])),
        "est_gap": (gap, float(limits["est_gap"])),
    }


def control(cell: Cell, run_: Run) -> Dict[str, Tuple[float, float]]:
    """The comparison applied to the control: the reference computed in
    float32, put in the program's place, against the float64 reference."""
    cfg = cell.config
    os_index, speed = run_.data["fleet"]
    events = run_.data["events"]
    want = reference(cfg, os_index, speed).replay(events)
    low = reference(cfg, os_index, speed, dtype=np.float32).replay(events)
    rows = [[0, "", 0.0, 0.0, 0.0, h, rt, "ok", [list(a[:3]) + [a[4], a[3]] for a in js]]
            for (h, rt), js in low.items()]
    return compare(rows, want, cell.traffic["limits"])
