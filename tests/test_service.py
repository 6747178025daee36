"""Asyncio service layer (§5.1): wire codec, malformed-frame rejection, and
the coalescing TCP front end-to-end.

Codec *property* round-trips live in ``test_protocol_property.py`` (behind
the hypothesis importorskip); this file pins deterministic examples, every
rejection code, and the asyncio service against a real project server.
"""
import asyncio

import pytest

from repro.core import (
    App,
    AppVersion,
    CompletedResult,
    Host,
    InstanceOutcome,
    Job,
    Platform,
    ProcessingResource,
    ProjectServer,
    ResourceRequest,
    ResourceType,
    ScheduleRequest,
    default_cpu_plan_class,
    next_id,
    reset_ids,
)
from repro.core.scheduler import TrickleUp
from repro.service import (
    MAX_LINE,
    ErrorReply,
    JobOffer,
    PingRequest,
    PongReply,
    ProtocolError,
    SchedulerService,
    StatsReply,
    StatsRequest,
    WorkReply,
    WorkRequest,
    decode_reply,
    decode_request,
    encode_reply,
    encode_request,
    run_load,
)

OSES = ("windows", "mac", "linux")


# ---------------------------------------------------------------------------
# codec: deterministic examples
# ---------------------------------------------------------------------------


class TestCodecExamples:
    def test_ping_stats_roundtrip(self):
        for req in (PingRequest(seq=7), StatsRequest(seq=0)):
            assert decode_request(encode_request(req)) == req
        for rep in (PongReply(seq=7), StatsReply(seq=3, values={"a b": 1.5})):
            assert decode_reply(encode_reply(rep)) == rep

    def test_work_request_roundtrip_full(self):
        sched = ScheduleRequest(
            host_id=42,
            requests={
                ResourceType.CPU: ResourceRequest(500.0, 1, 80.5),
                ResourceType.GPU: ResourceRequest(1000.0, 0, 0.0),
            },
            completed=[
                CompletedResult(
                    instance_id=9,
                    outcome=InstanceOutcome.SUCCESS,
                    runtime=123.456,
                    peak_flop_count=1e12,
                    exit_code=0,
                ),
                CompletedResult(
                    instance_id=10,
                    outcome=InstanceOutcome.CLIENT_ERROR,
                    exit_code=-9,
                ),
            ],
            trickles=[TrickleUp(instance_id=9, fraction_done=0.25)],
            sticky_files=("a b.dat", "comma,colon:.bin", "uni⊕code"),
            usable_disk=5e11,
        )
        wire = encode_request(WorkRequest(seq=3, request=sched))
        back = decode_request(wire)
        assert isinstance(back, WorkRequest)
        assert back.seq == 3
        assert back.request == sched

    def test_work_reply_roundtrip(self):
        rep = WorkReply(
            seq=11,
            request_delay=6.5,
            jobs=[JobOffer(1, 2, 3, 100.25, 1e12)],
            delete_sticky=["old file.dat"],
        )
        assert decode_reply(encode_reply(rep)) == rep

    def test_error_reply_roundtrip(self):
        rep = ErrorReply(seq=0, code="bad-frame", message="what is this?")
        assert decode_reply(encode_reply(rep)) == rep

    def test_float_fidelity_and_nonfinite(self):
        # repr/float is the identity on doubles, inf included
        vals = (0.1 + 0.2, 1e-308, float("inf"), -0.0)
        sched = ScheduleRequest(
            host_id=1,
            requests={ResourceType.CPU: ResourceRequest(vals[0], vals[1], vals[2])},
            usable_disk=vals[3],
        )
        back = decode_request(encode_request(WorkRequest(seq=1, request=sched)))
        rr = back.request.requests[ResourceType.CPU]
        assert (rr.req_runtime, rr.req_idle, rr.queue_dur) == vals[:3]
        assert str(back.request.usable_disk) == "-0.0"


class TestMalformedFrames:
    @pytest.mark.parametrize(
        "line,code",
        [
            ("", "bad-frame"),
            ("PING", "bad-frame"),
            ("PING x", "bad-int"),
            ("NOPE 1", "bad-verb"),
            ("PING 1 extra", "bad-field"),
            ("STATS 1 v=1", "bad-field"),
            ("WORK 1 host=1", "bad-field"),  # missing disk
            ("WORK 1 disk=0.0", "bad-field"),  # missing host
            ("WORK 1 host=abc disk=0.0", "bad-int"),
            ("WORK 1 host=1 disk=abc", "bad-float"),
            ("WORK 1 host=1 disk=0.0 host=2", "bad-field"),  # duplicate key
            ("WORK 1 host=1 disk=0.0 bogus=3", "bad-field"),
            ("WORK 1 host=1 disk=0.0 cpu=1.0:2.0", "bad-field"),  # 3 cols
            ("WORK 1 host=1 disk=0.0 done=", "bad-field"),  # empty list
            ("WORK 1 host=1 disk=0.0 done=1:2:3", "bad-field"),  # 5 cols
            ("WORK 1 host=1 disk=0.0 done=1:weird:0.0:0.0:0", "bad-field"),
            ("WORK 1 host=1 disk=0.0 trickle=1", "bad-field"),
            ("W" * (MAX_LINE + 1), "too-long"),
        ],
    )
    def test_request_rejection(self, line, code):
        with pytest.raises(ProtocolError) as e:
            decode_request(line)
        assert e.value.code == code

    @pytest.mark.parametrize(
        "line,code",
        [
            ("WAT 1", "bad-verb"),
            ("JOBS 1", "bad-field"),  # missing delay
            ("JOBS 1 delay=x", "bad-float"),
            ("JOBS 1 delay=0.0 job=1:2:3", "bad-field"),
            ("ERR 1 code", "bad-field"),  # missing message
            ("PONG 1 extra", "bad-field"),
        ],
    )
    def test_reply_rejection(self, line, code):
        with pytest.raises(ProtocolError) as e:
            decode_reply(line)
        assert e.value.code == code


# ---------------------------------------------------------------------------
# the asyncio service end-to-end
# ---------------------------------------------------------------------------


def _make_project(n_sched=4, vector=True, cache_size=48, n_jobs=200, n_hosts=64):
    reset_ids()
    server = ProjectServer(
        name="svc",
        cache_size=cache_size,
        n_scheduler_instances=n_sched,
        vector_dispatch=vector,
    )
    app = App(name="a", min_quorum=1, init_ninstances=1)
    for osn in OSES:
        app.add_version(
            AppVersion(
                id=next_id("appver"),
                app_name="a",
                platform=Platform(osn, "x86_64"),
                version_num=1,
                plan_class=default_cpu_plan_class(),
            )
        )
    server.add_app(app)
    for _ in range(n_jobs):
        server.submit_job(
            Job(id=next_id("job"), app_name="a", est_flop_count=1e12), 0.0
        )
    for i in range(n_hosts):
        server.add_host(
            Host(
                id=i + 1,
                platforms=(Platform(OSES[i % 3], "x86_64"),),
                resources={
                    ResourceType.CPU: ProcessingResource(ResourceType.CPU, 4, 2e10)
                },
                volunteer_id=i + 1,
            )
        )
    server.tick(0.0)
    return server


class TestSchedulerService:
    def test_coalesced_load(self):
        server = _make_project()

        async def main():
            svc = SchedulerService(server, coalesce=True, max_batch=256)
            await svc.start()
            try:
                report = await run_load(
                    "127.0.0.1", svc.port, n_clients=200, n_conns=16,
                    host_ids=list(range(1, 65)),
                )
            finally:
                await svc.stop()
            return report, svc.stats()

        report, stats = asyncio.run(main())
        assert report.replies == report.requests == 200
        assert report.errors == 0
        assert report.jobs_received > 0
        assert stats["requests"] == 200
        # concurrent clients actually coalesced into rpc_batch waves
        assert stats["max_wave"] > 1
        assert stats["waves"] < 200
        # the sharded project reports per-shard utilization
        shard_reqs = [row["requests"] for row in stats["shards"]]
        assert sum(shard_reqs) == 200
        assert all(r > 0 for r in shard_reqs)

    def test_sequential_baseline_mode(self):
        server = _make_project(n_sched=1, vector=False)

        async def main():
            svc = SchedulerService(server, coalesce=False)
            await svc.start()
            try:
                report = await run_load("127.0.0.1", svc.port, n_clients=30,
                                        n_conns=4)
            finally:
                await svc.stop()
            return report

        report = asyncio.run(main())
        assert report.replies == 30
        assert report.errors == 0
        assert report.jobs_received > 0

    def test_ping_stats_and_error_frames_inline(self):
        server = _make_project(n_sched=1, n_jobs=10, n_hosts=4)

        async def main():
            svc = SchedulerService(server)
            await svc.start()
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", svc.port
                )
                writer.write(b"PING 5\n")
                writer.write(b"this is not a frame\n")  # ERR, conn survives
                writer.write(b"STATS 6\n")
                await writer.drain()
                lines = [await reader.readline() for _ in range(3)]
                writer.close()
            finally:
                await svc.stop()
            return [decode_reply(l.decode().rstrip("\n")) for l in lines]

        pong, err, stats = asyncio.run(main())
        assert pong == PongReply(seq=5)
        assert isinstance(err, ErrorReply) and err.code == "bad-int"
        assert isinstance(stats, StatsReply)
        assert stats.values["errors"] == 1.0

    def test_work_frame_reports_completions(self):
        # a done= report flows through the real scheduler: the instance
        # leaves IN_PROGRESS and the reply still offers new work
        server = _make_project(n_sched=2, n_jobs=40, n_hosts=8)

        async def main():
            svc = SchedulerService(server)
            await svc.start()
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", svc.port
                )

                async def ask(seq, host_id, done=""):
                    line = f"WORK {seq} host={host_id} disk=1e+15 cpu=3000.0:1.0:0.0"
                    if done:
                        line += f" done={done}"
                    writer.write((line + "\n").encode())
                    await writer.drain()
                    return decode_reply((await reader.readline()).decode().rstrip("\n"))

                first = await ask(1, 2)
                assert first.jobs
                inst = first.jobs[0].instance_id
                second = await ask(
                    2, 2, done=f"{inst}:success:120.0:1e+12:0"
                )
                writer.close()
            finally:
                await svc.stop()
            return inst, second

        inst_id, second = asyncio.run(main())
        assert isinstance(second, WorkReply)
        inst = server.store.instances[inst_id]
        assert not inst.is_outstanding()

    def test_stats_export_queue_wait_and_compiles(self):
        # queue_wait_s sums, over dispatched requests, the time from the
        # frame's enqueue to the start of its wave: it never falls and
        # grows with every request that passes through the queue
        server = _make_project(n_sched=1, n_jobs=60, n_hosts=8)

        async def main():
            svc = SchedulerService(server)
            await svc.start()
            try:
                reader, writer = await asyncio.open_connection("127.0.0.1", svc.port)
                seq = 0

                async def stats():
                    nonlocal seq
                    seq += 1
                    writer.write(f"STATS {seq}\n".encode())
                    await writer.drain()
                    return decode_reply((await reader.readline()).decode().rstrip("\n"))

                async def work(n):
                    nonlocal seq
                    for _ in range(n):
                        seq += 1
                        writer.write(
                            f"WORK {seq} host={seq % 8 + 1} disk=1e+15 "
                            f"cpu=3000.0:1.0:0.0\n".encode()
                        )
                    await writer.drain()
                    for _ in range(n):
                        await reader.readline()

                before = await stats()
                await work(1)
                one = await stats()
                await work(12)
                many = await stats()
                writer.close()
            finally:
                await svc.stop()
            return before, one, many, svc.stats()

        before, one, many, direct = asyncio.run(main())
        assert before.values["queue_wait_s"] == 0.0
        assert one.values["requests"] == 1.0 and one.values["queue_wait_s"] > 0.0
        assert many.values["requests"] == 13.0
        assert many.values["queue_wait_s"] > one.values["queue_wait_s"]
        assert many.values["compiles"] >= 0.0
        assert direct["queue_wait_s"] == many.values["queue_wait_s"]
        assert isinstance(direct["compiles"], int)
