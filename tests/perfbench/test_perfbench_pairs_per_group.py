"""``validate.pairs_per_group`` on hand-made spans: the comparisons of the
validation pass over the jobs it grouped one at a time, inside the window,
and nothing on a program that opens no group span."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

pytest.importorskip("jax")

from perfbench.harness import trace  # noqa: E402
from perfbench.harness.cell import Run, load_benchmark, load_module  # noqa: E402

MS = 1e6
NAME = "validate.pairs_per_group"


def _run(program):
    return Run(setup_s=1.0, end_to_end={}, attempted=6, failed=0, checks={},
               device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               window=(0.0, 1.0), trace=trace.TraceEvents(), trace_offset_ns=0.0,
               data={"program_spans": program, "passes": [(0.1, 0.9, 3, 5)]},
               device_planes=["/device:TPU:0"])


def _job(t0, pairs):
    """A job's group span at ``t0`` ms and its ``pairs`` comparisons."""
    out = [("boinc.validate.group", t0 * MS, (t0 + 10) * MS)]
    out += [("boinc.validate.pair", (t0 + 1 + 2 * k) * MS, (t0 + 2 + 2 * k) * MS)
            for k in range(pairs)]
    return out


def _read(program):
    return load_module("metrics", NAME).read(_run(program))


def test_the_entry_reads_the_validate_cell():
    entry, = [m for m in load_benchmark(ROOT)["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == ["mamba2_grad_quorum.validate"]
    assert entry["moves"] == "validated_per_s"


@pytest.mark.parametrize("pairs,want", [
    ([1, 1], 1.0),
    ([1, 3, 2], 2.0),
    ([1] * 23 + [3] * 3 + [2] * 3 + [3] * 3, 47 / 32),  # the validate cell's pass
])
def test_pairs_over_groups(pairs, want):
    program = [s for k, n in enumerate(pairs) for s in _job(100 + 20 * k, n)]
    assert _read(program) == pytest.approx(want)


def test_only_spans_that_start_in_the_window_count():
    # two jobs inside, one whose group starts after the window closes, and
    # a pair left over from before it opened
    program = (_job(100, 1) + _job(200, 3) + _job(1005, 2)
               + [("boinc.validate.pair", -5 * MS, -4 * MS)])
    assert _read(program) == pytest.approx((1 + 3) / 2)


@pytest.mark.parametrize("program", [
    [],
    # the parent's tick-wide grouping: comparisons, but no group span
    [("boinc.validate.stack", 100 * MS, 104 * MS),
     ("boinc.validate.pair", 110 * MS, 115 * MS),
     ("boinc.validate.upload", 110 * MS, 111 * MS)],
])
def test_nothing_to_read_without_group_spans(program):
    assert _read(program) is None


def test_a_group_with_no_comparison_reads_zero():
    assert _read(_job(100, 0)) == 0.0
