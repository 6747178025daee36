"""The program's own spans as the benchmark reads them: a 4-shard
coalescing service recorded under the profiler on the CPU, the nine
readers of the program's per-layer metrics on hand-made intervals, and the
readers the benchmark had before, unmoved by the program's spans."""
import asyncio
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

pytest.importorskip("jax")

from perfbench.harness import program_spans, report, trace  # noqa: E402
from perfbench.harness.cell import Cell, Run, load_benchmark, load_module  # noqa: E402
from perfbench.harness.counters import Spans  # noqa: E402

NEW = {
    "front.queue_wait_ms.served", "front.codec_us.served", "server.shard_pass_ms.served",
    "dispatch.snapshot_build_ms.served", "dispatch.device_wait_ms.served",
    "device.idle_host_busy.served", "validate.pair_ms", "validate.upload_ms_per_pair",
    "validate.stack_ms_per_job",
}


# -- the service under the profiler ---------------------------------------------


def _project():
    from repro.core import (App, AppVersion, Host, Job, Platform, ProcessingResource,
                            ProjectServer, ResourceType, default_cpu_plan_class,
                            next_id, reset_ids)

    cpu = ResourceType.CPU
    reset_ids()
    server = ProjectServer(name="traced", cache_size=64, n_scheduler_instances=4,
                           vector_dispatch=True, engine_backend="jax")
    app = App(name="a", min_quorum=1, init_ninstances=1)
    oses = ("windows", "mac", "linux")
    for osn in oses:
        app.add_version(AppVersion(id=next_id("appver"), app_name="a",
                                   platform=Platform(osn, "x86_64"), version_num=1,
                                   plan_class=default_cpu_plan_class()))
    server.add_app(app)
    for _ in range(300):
        server.submit_job(Job(id=next_id("job"), app_name="a", est_flop_count=1e12), 0.0)
    for i in range(64):
        server.add_host(Host(id=i + 1, platforms=(Platform(oses[i % 3], "x86_64"),),
                             resources={cpu: ProcessingResource(cpu, 4, 2e10)},
                             volunteer_id=i + 1))
    server.tick(0.0)
    return server


def _benchmark_spans(server, spans):
    """``pb.rpc_batch`` around each call of the service into the project,
    as the served driver's recorder puts them."""
    batch, one = server.rpc_batch, server.rpc

    def rpc_batch(requests, now):
        with spans.span("rpc_batch"):
            return batch(requests, now)

    def rpc(request, now):
        with spans.span("rpc_batch"):
            return one(request, now)

    server.rpc_batch, server.rpc = rpc_batch, rpc


def test_service_spans_are_program_spans_and_nest(tmp_path):
    from repro.service import SchedulerService, run_load

    server = _project()
    _benchmark_spans(server, Spans())

    async def main():
        svc = SchedulerService(server, coalesce=True, max_batch=64, refill_every=32)
        await svc.start()
        try:
            return await run_load("127.0.0.1", svc.port, n_clients=96, n_conns=12,
                                  host_ids=list(range(1, 65)))
        finally:
            await svc.stop()

    trace.start(str(tmp_path))
    try:
        load = asyncio.run(main())
    finally:
        path = trace.stop(str(tmp_path))
    assert load.replies == 96 and load.errors == 0
    events = trace.load(path)
    prog = program_spans.load(path)
    names = {s[0] for s in prog}
    for name in ("boinc.svc.decode", "boinc.svc.encode", "boinc.server.rpc_batch",
                 "boinc.server.shard_pass", "boinc.dispatch.device",
                 "boinc.sched.snapshot_build", "boinc.feeder.fill"):
        assert name in names
    assert all(s[0].startswith(program_spans.PREFIX) for s in prog)
    assert not any(s[0].startswith(program_spans.PREFIX) for s in events.spans)
    assert sum(s[0] == "boinc.svc.decode" for s in prog) == 96

    def named(name):
        return [s for s in prog if s[0] == name]

    def within(child, parents):
        return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)

    calls, passes = named("boinc.server.rpc_batch"), named("boinc.server.shard_pass")
    assert all(within(d, passes) for d in named("boinc.dispatch.device"))
    assert all(within(p, calls) for p in passes)
    # each benchmark span and its program twin open and close together
    twins = sorted(s for s in calls if not within(s, [c for c in calls if c != s]))
    ours = sorted(trace.spans_named(events.spans, "rpc_batch"), key=lambda s: s[1])
    assert len(twins) == len(ours) > 1
    for (_, a0, a1), (_, b0, b1) in zip(ours, twins):
        assert abs(a0 - b0) < 5e6 and abs(a1 - b1) < 5e6


def test_a_span_with_metadata_reads_by_its_bare_name(tmp_path):
    import jax

    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("boinc.server.shard_pass", shard=2, requests=5):
        pass
    with jax.profiler.TraceAnnotation("pb.clock"):
        pass
    path = trace.stop(str(tmp_path))
    (name, t0, t1), = program_spans.load(path)
    assert name == "boinc.server.shard_pass" and t1 >= t0
    assert [s[0] for s in trace.load(path).spans] == ["pb.clock"]


# -- the readers on hand-made intervals --------------------------------------------

MS = 1e6  # ns


def _served_run(program):
    """A traced served window of 1 s (trace clock = perf_counter ns) with
    two device ops, 10 requests in 4 waves and the given program spans."""
    events = trace.TraceEvents(
        ops={"/device:TPU:0": [("fusion", 100 * MS, 110 * MS), ("copy", 500 * MS, 510 * MS)]},
        modules={"/device:TPU:0": [("jit_f", 100 * MS, 110 * MS)]},
        spans=[("pb.rpc_batch", 90 * MS, 200 * MS), ("pb.rpc_batch", 480 * MS, 600 * MS)],
    )
    marks = {"w0": (1.0, {"waves": 2, "requests": 5, "queue_wait_s": 0.5}),
             "w1": (2.0, {"waves": 6, "requests": 15, "queue_wait_s": 0.54})}
    return Run(setup_s=1.0, end_to_end={}, attempted=10, failed=0, checks={},
               device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               window=(0.0, 1.0), trace=events, trace_offset_ns=0.0,
               data={"marks": marks, "program_spans": program,
                     "waves": [(0.09, 0.2, 4), (0.48, 0.6, 6)]},
               device_planes=["/device:TPU:0"])


SERVED_SPANS = [
    ("boinc.svc.decode", 10 * MS, 10.03 * MS),
    ("boinc.svc.decode", 20 * MS, 20.05 * MS),
    ("boinc.svc.encode", 300 * MS, 300.12 * MS),
    ("boinc.server.rpc_batch", 95 * MS, 195 * MS),
    ("boinc.server.shard_pass", 96 * MS, 104 * MS),
    ("boinc.server.shard_pass", 150 * MS, 156 * MS),
    ("boinc.sched.snapshot_build", 96 * MS, 99 * MS),
    ("boinc.dispatch.device", 100 * MS, 101 * MS),
    ("boinc.dispatch.device", 150 * MS, 152 * MS),
    ("boinc.feeder.fill", 700 * MS, 720 * MS),
    ("boinc.server.shard_pass", 1500 * MS, 1510 * MS),  # after the window: not counted
]


def _validate_run(program):
    return Run(setup_s=1.0, end_to_end={}, attempted=6, failed=0, checks={},
               device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               window=(0.0, 1.0), trace=trace.TraceEvents(), trace_offset_ns=0.0,
               data={"program_spans": program,
                     "passes": [(0.1, 0.5, 2, 5), (0.5, 0.9, 2, 4), (1.2, 1.5, 2, 4)]},
               device_planes=["/device:TPU:0"])


VALIDATE_SPANS = [
    ("boinc.validate.stack", 100 * MS, 104 * MS),
    ("boinc.validate.stack", 104 * MS, 105 * MS),
    ("boinc.validate.stack", 500 * MS, 503 * MS),
    ("boinc.validate.pair", 110 * MS, 300 * MS),
    ("boinc.validate.upload", 110 * MS, 150 * MS),
    ("boinc.validate.pair", 510 * MS, 710 * MS),
    ("boinc.validate.upload", 510 * MS, 530 * MS),
]

EXPECT = {
    # (40 ms queued over 10 requests)
    "front.queue_wait_ms.served": pytest.approx(4.0),
    # (30 + 50 + 120 us) / 10 requests
    "front.codec_us.served": pytest.approx(20.0),
    "server.shard_pass_ms.served": pytest.approx(7.0),
    "dispatch.snapshot_build_ms.served": pytest.approx(3.0),
    # 3 ms / 10 requests
    "dispatch.device_wait_ms.served": pytest.approx(0.3),
    # idle 980 ms; spans cover 0.08 + 100 (rpc_batch, minus the 10 ms op
    # inside it) - 10 + 0.12 + 20 ms of it
    "device.idle_host_busy.served": pytest.approx(100 * (0.08 + 90 + 0.12 + 20) / 980),
    "validate.pair_ms": pytest.approx(195.0),
    "validate.upload_ms_per_pair": pytest.approx(30.0),
    # 8 ms over the 4 jobs of the two passes that start in the window
    "validate.stack_ms_per_job": pytest.approx(2.0),
}


def _run_for(name, program=None):
    if name.startswith("validate."):
        return _validate_run(VALIDATE_SPANS if program is None else program)
    return _served_run(SERVED_SPANS if program is None else program)


def test_the_nine_readers_are_the_nine_new_entries():
    bench = load_benchmark(ROOT)
    assert NEW == set(EXPECT) <= {m["name"] for m in bench["per_layer"]}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_program_reader_on_hand_made_intervals(name):
    assert load_module("metrics", name).read(_run_for(name)) == EXPECT[name]


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_program_reader_is_silent_on_a_program_without_its_spans(name):
    # a program that opens no boinc.* span and exports no queue_wait_s
    run = _run_for(name, program=[])
    for _, stats in run.data.get("marks", {}).values():
        stats.pop("queue_wait_s")
    assert load_module("metrics", name).read(run) is None


def test_earlier_readers_and_breakdown_ignore_program_spans():
    bench = load_benchmark(ROOT)
    old = [m["name"] for m in bench["per_layer"] if m["name"] not in NEW
           and "s11_fleet.served" in m["workloads"] and not m["name"].startswith("loadgen.")]
    cell = Cell(name="s11_fleet.served", config={}, traffic={}, chips=1, end_to_end={},
                per_layer={n: "x" for n in old})
    bare, traced = _served_run([]), _served_run(SERVED_SPANS)
    assert report.result(cell, bare, trace=True) == report.result(cell, traced, trace=True)
    assert report.breakdown(traced) == report.breakdown(bare)
    assert set(report.result(cell, traced, trace=True)["metrics"]) == set(old)
