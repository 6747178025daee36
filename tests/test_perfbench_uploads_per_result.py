"""``validate.uploads_per_result`` (``perfbench/metrics``): the validation
pass's put spans over the results of its passes, on hand-made spans and on
the spans the Pallas route records under the profiler on the CPU; nothing
on a program that opens no put span."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

jax = pytest.importorskip("jax")

from perfbench.harness import program_spans, trace  # noqa: E402
from perfbench.harness.cell import Run, load_benchmark, load_module  # noqa: E402
from repro.core import jax_backend  # noqa: E402

MS = 1e6
NAME = "validate.uploads_per_result"


def _run(program, passes, window=(0.0, 1.0)):
    return Run(setup_s=1.0, end_to_end={}, attempted=7, failed=0, checks={},
               device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               window=window, trace=trace.TraceEvents(), trace_offset_ns=0.0,
               data={"program_spans": program, "passes": passes},
               device_planes=["/device:TPU:0"])


def _read(run):
    return load_module("metrics", NAME).read(run)


def _puts(*t0_ms):
    return [("boinc.validate.put", t * MS, (t + 1) * MS) for t in t0_ms]


def test_the_entry_reads_the_validate_cell():
    entry, = [m for m in load_benchmark(ROOT)["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == ["mamba2_grad_quorum.validate"]
    assert entry["moves"] == "validated_per_s"
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "uploads/result", "lower", "program_span")


@pytest.mark.parametrize("puts,want", [
    (_puts(110, 120, 130, 510, 520), 1.0),
    (_puts(110, 111, 120, 130, 510, 520, 530, 540, 550, 560), 2.0),
    # a put before the window opened and one after it closed do not count
    (_puts(-5, 110, 120, 510, 520, 1200), 4 / 5),
])
def test_puts_over_the_results_of_the_passes_in_the_window(puts, want):
    # two passes of 3 and 2 results in the window, one after it
    passes = [(0.1, 0.5, 2, 3), (0.5, 0.9, 1, 2), (1.1, 1.5, 1, 2)]
    assert _read(_run(puts, passes)) == pytest.approx(want)


@pytest.mark.parametrize("program", [
    [],
    # the parent's route: a stack span a job, no put span
    [("boinc.validate.stack", 100 * MS, 104 * MS),
     ("boinc.validate.pair", 110 * MS, 115 * MS)],
])
def test_nothing_to_read_without_put_spans(program):
    assert _read(_run(program, [(0.1, 0.5, 2, 5)])) is None


def test_the_route_reads_one_upload_a_result(tmp_path):
    # three jobs of 2, 3 and 2 float32 results under the profiler
    rng = np.random.default_rng(8)
    a, b, c = (rng.standard_normal(600).astype(np.float32) for _ in range(3))
    outputs = [a, a.copy(), b, b.copy(), b + 5.0, c, c.copy()]
    fn = jax_backend.fuzzy_digest_jax(lambda outs: 1 / 0, 1e-4, 1e-6)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn(outputs, job_off=[0, 2, 5, 7])
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    run = _run(program_spans.load(str(path)), [(1.0, 2.0, 3, 7)],
               window=(0.0, float("inf")))
    assert _read(run) == 1.0
