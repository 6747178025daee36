"""The chip benchmark's harness on the CPU: trace reduction, the peak
table, the result line, the refusal to run without a TPU, and finding a
new traffic mix by name alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

pytest.importorskip("jax")

from perfbench.harness import cell as cellmod  # noqa: E402
from perfbench.harness import device, report, trace  # noqa: E402
from perfbench.harness.cell import Cell, Run  # noqa: E402
from perfbench.harness.counters import GcLog, Spans, float_diff, percentile  # noqa: E402


# -- trace reduction ----------------------------------------------------------


OPS = [("a", 0.0, 10.0), ("b", 5.0, 15.0), ("a", 20.0, 30.0), ("c", 40.0, 45.0)]


def test_busy_is_the_union_of_op_intervals():
    assert trace.merge(OPS, 0.0, 50.0) == [(0.0, 15.0), (20.0, 30.0), (40.0, 45.0)]
    assert trace.busy(OPS, 0.0, 50.0) == 30.0
    assert trace.busy(OPS, 8.0, 25.0) == 12.0  # clipped to the window


def test_device_time_by_name_sums_and_sorts():
    assert trace.time_by_name(OPS, 0.0, 50.0) == [("a", 20.0), ("b", 10.0), ("c", 5.0)]


def test_idle_gaps_are_attributed_to_the_innermost_host_span():
    spans = [("pb.window", 0.0, 50.0), ("pb.rpc_batch", 14.0, 21.0),
             ("pb.feeder_fill", 31.0, 39.0)]
    assert trace.idle_gaps(OPS, 0.0, 50.0) == [(15.0, 20.0), (30.0, 40.0), (45.0, 50.0)]
    gaps = trace.longest_gaps(OPS, spans, 0.0, 50.0, k=2)
    assert gaps == [("pb.feeder_fill", 10.0), ("pb.rpc_batch", 5.0)]
    assert trace.longest_gaps(OPS, spans, 0.0, 50.0, k=3)[2] == ("pb.window", 5.0)


def test_ops_inside_spans():
    spans = [("pb.x", 4.0, 21.0)]
    assert trace.inside(OPS, spans) == [("b", 5.0, 15.0), ("a", 20.0, 30.0)]


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    spans = Spans()
    trace.start(str(tmp_path))
    with spans.span("clock"):
        pass
    with spans.span("outer"):
        for _ in range(3):
            with spans.span("inner"):
                f(x).block_until_ready()
    events = trace.load(trace.stop(str(tmp_path)))
    names = [s[0] for s in events.spans]
    assert names.count("pb.inner") == 3 and names.count("pb.outer") == 1
    assert events.ops == {}  # the CPU has no TPU plane
    offset = trace.clock_offset(events, spans.records)
    (_, o0, o1), = trace.spans_named(events.spans, "outer")
    mine = [(a, b) for n, a, b in spans.records if n == "outer"][0]
    assert abs((mine[0] * 1e9 + offset) - o0) < 5e6  # clocks agree within 5 ms
    # the inner spans stand in for device ops: busy is their union, the
    # gaps between them lie in the outer span
    inner = trace.spans_named(events.spans, "inner")
    assert trace.busy(inner, o0, o1) == pytest.approx(sum(b - a for _, a, b in inner))
    for name, _ in trace.longest_gaps(inner, events.spans, o0, o1, k=2,
                                      exclude=("pb.inner",)):
        assert name == "pb.outer"


# -- peaks, statistics ----------------------------------------------------------


def test_peak_table_refuses_an_unknown_device_kind():
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("TPU v99")
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_require_chips_refuses_the_cpu():
    with pytest.raises(device.NoAccelerator, match="needs a TPU"):
        device.require_chips(1)


def test_gc_log_records_collections_by_generation():
    import gc
    import time

    t0 = time.perf_counter()
    with GcLog() as log:
        gc.collect()
    gc.collect()  # after the log is left: not recorded
    t1 = time.perf_counter()
    assert [g for g, _, _ in log.records] == [2]
    count, total_ms, longest_ms = log.summary(t0, t1)["2"]
    assert count == 1 and total_ms == longest_ms > 0
    assert log.summary(t1, t1 + 1) == {}


def test_percentile_and_float_diff():
    vals = list(range(1, 101))
    assert percentile(vals, 0.99) == 99
    assert percentile(vals, 0.5) == 50
    assert percentile([1.0, float("inf")], 0.99) == float("inf")
    assert float_diff([1.0, 2.0], [1.0, 2.0]) == (0, 0.0)
    n, rel = float_diff([1.0, 2.0], [1.0, 2.0 * (1 + 1e-12)])
    assert n == 1 and rel == pytest.approx(1e-12, rel=1e-3)


# -- the result line ---------------------------------------------------------------


def _cell():
    return Cell(name="x.y", config={}, traffic={}, chips=1,
                end_to_end={"rpc_per_s": "rpc/s", "setup_s": "s"},
                per_layer={"front.wave_size.served": "req/wave"})


def test_last_line_keys_and_checks_last(capsys):
    run = Run(setup_s=3.0, end_to_end={"rpc_per_s": 10.0, "setup_s": 3.0}, attempted=5,
              failed=0, checks={"reply_mismatch": (0.0, 0.0), "est_gap": (2e-15, 1e-10)},
              device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1}, window=(0.0, 1.0))
    line = report.result(_cell(), run, trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True
    assert line["metrics"]["rpc_per_s"] == {"value": 10.0, "unit": "rpc/s"}
    report.emit(line)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == line
    assert err.strip().splitlines()[-1].startswith("check est_gap = 2e-15 limit 1e-10 ok")
    run.checks["reply_mismatch"] = (1.0, 0.0)
    assert report.result(_cell(), run, trace=False)["correct"] is False


def test_traced_line_has_busy_window_and_breakdown():
    events = trace.TraceEvents(
        ops={"/device:TPU:0": [("fusion", 1e9, 1.5e9), ("copy", 2e9, 2.25e9)]},
        modules={"/device:TPU:0": [("jit_f", 1e9, 1.5e9)]},
        spans=[("pb.rpc_batch", 0.9e9, 1.6e9), ("pb.rpc_batch", 1.9e9, 2.3e9)],
    )
    run = Run(setup_s=1.0, end_to_end={}, attempted=2, failed=0,
              checks={"unanswered": (0.0, 0.0)},
              device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1},
              window=(1.0, 3.0), trace=events, trace_offset_ns=0.0,
              data={"marks": {"w0": (1.0, {"waves": 1, "requests": 1}),
                              "w1": (3.0, {"waves": 3, "requests": 9})}},
              device_planes=["/device:TPU:0"])
    line = report.result(_cell(), run, trace=True)
    assert line["device"]["busy_s"] == pytest.approx(0.75)
    assert line["device"]["window_s"] == pytest.approx(2.0)
    assert line["metrics"] == {"front.wave_size.served": {"value": 4.0, "unit": "req/wave"}}
    assert [n for n, _ in line["breakdown"]["device_ops"]] == ["fusion", "copy"]
    assert line["breakdown"]["idle_gaps"][0] == ["(no span)", pytest.approx(0.75)]
    assert list(line)[-1] == "checks"


# -- the command -------------------------------------------------------------------


def test_run_refuses_the_cpu_before_any_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         "s11_fleet.served", "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_every_cell_metric_and_file_is_found_by_name():
    bench = cellmod.load_benchmark(ROOT)
    for w in bench["workloads"]:
        c = cellmod.find_cell(w["name"], ROOT)
        assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
        assert c.per_layer
        for name in c.per_layer:
            assert callable(cellmod.load_module("metrics", name).read)
        assert (ROOT / "perfbench" / "drivers" / f"{c.traffic['driver']}.py").exists()


def test_a_new_mix_needs_only_a_data_file_and_an_entry(tmp_path):
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "perfbench").rglob("*") if p.is_file()}
    mix = json.loads((ROOT / "perfbench" / "traffic" / "served.json").read_text())
    mix["rate_per_s"] = 7.0
    (tmp_path / "perfbench" / "traffic" / "served_slow.json").write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "s11_fleet.served_slow", "config": "s11_fleet",
                               "traffic": "served_slow", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("s11_fleet.served_slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = cellmod.find_cell("s11_fleet.served_slow", tmp_path)
    assert c.traffic["rate_per_s"] == 7.0 and c.traffic["driver"] == "served"
    assert c.config["hosts"] == 700000
    assert set(c.end_to_end) == {"rpc_p50_ms", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file the benchmark had was touched
