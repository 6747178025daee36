"""The program's own spans and compile counter (``core/tracing.py``): the
validation pass's spans as the profiler records them on the CPU, the
no-JAX fallback, and the one compile counter ``chip_smoke.py`` shares."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import jax_backend, tracing  # noqa: E402


def _record(fn, log_dir):
    """Run ``fn`` under the profiler; (its result, the ``boinc.*`` host
    spans of the trace as (name, t0, t1, stats))."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    spans = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("boinc.")
    ]
    return out, spans


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_quorum_group_codes_spans_a_corrupt_honest_honest_job(tmp_path):
    # replica 0 corrupt, 1 and 2 honest: row 1 founds a second group after
    # one comparison, row 2 is compared with both representatives
    rng = np.random.default_rng(7)
    honest = rng.standard_normal(3000).astype(np.float32)
    corrupt = honest.copy()
    corrupt[[5, 1700, 2999]] += 1.5
    mat = np.stack([corrupt, honest, honest * np.float32(1 + 1e-7)])
    want = jax_backend.quorum_group_codes(mat, 1e-4, 1e-6)
    codes, spans = _record(lambda: jax_backend.quorum_group_codes(mat, 1e-4, 1e-6),
                           tmp_path)
    assert codes.tolist() == want.tolist() == [0, 1, 1]
    by = {n: [s for s in spans if s[0] == n] for n in
          ("boinc.validate.pair", "boinc.validate.upload", "boinc.validate.stack")}
    assert len(by["boinc.validate.pair"]) == 3
    assert len(by["boinc.validate.upload"]) == 3
    for pair in by["boinc.validate.pair"]:
        assert sum(_inside(u, pair) for u in by["boinc.validate.upload"]) == 1
    assert len(by["boinc.validate.stack"]) == 1
    assert not any(_inside(s, p) for s in by["boinc.validate.stack"]
                   for p in by["boinc.validate.pair"])


def test_each_job_is_grouped_in_a_span_of_its_own(tmp_path):
    # three jobs of 2, 3 and 2 rows through the engine's pairwise hook
    rng = np.random.default_rng(8)
    a, b, c = (rng.standard_normal(600).astype(np.float32) for _ in range(3))
    bad = b.copy()
    bad[599] += 2.0
    outputs = [a, a, bad, b, b, c, c]
    fn = jax_backend.fuzzy_digest_jax(lambda outs: 1 / 0, 1e-4, 1e-6)
    codes, spans = _record(lambda: fn(outputs, job_off=[0, 2, 5, 7]), tmp_path)
    assert codes.tolist() == [0, 0, 0, 1, 1, 0, 0]
    groups = [s for s in spans if s[0] == "boinc.validate.group"]
    pairs = [s for s in spans if s[0] == "boinc.validate.pair"]
    assert [g[3] for g in groups] == [{"rows": 2}, {"rows": 3}, {"rows": 2}]
    assert [sum(_inside(p, g) for p in pairs) for g in groups] == [1, 3, 1]
    assert len(pairs) == 5


def test_each_result_is_put_on_the_device_once_inside_a_stack_span(tmp_path):
    # the same three jobs: seven results, one put span each, all inside the
    # stack span of their own job
    rng = np.random.default_rng(8)
    a, b, c = (rng.standard_normal(600).astype(np.float32) for _ in range(3))
    bad = b.copy()
    bad[599] += 2.0
    outputs = [a, a.copy(), bad, b, b.copy(), c, c.copy()]
    fn = jax_backend.fuzzy_digest_jax(lambda outs: 1 / 0, 1e-4, 1e-6)
    codes, spans = _record(lambda: fn(outputs, job_off=[0, 2, 5, 7]), tmp_path)
    assert codes.tolist() == [0, 0, 0, 1, 1, 0, 0]
    puts = [s for s in spans if s[0] == "boinc.validate.put"]
    stacks = [s for s in spans if s[0] == "boinc.validate.stack"]
    assert len(puts) == 7
    assert all(sum(_inside(p, s) for s in stacks) == 1 for p in puts)
    per_stack = [sum(_inside(p, s) for p in puts) for s in sorted(stacks, key=lambda s: s[1])]
    assert [k for k in per_stack if k] == [2, 3, 2]


def test_a_span_records_its_metadata_as_stats(tmp_path):
    def body():
        with tracing.span("boinc.test.outer", shard=3):
            with tracing.span("boinc.test.inner") as sp:
                sp.set_metadata(seq=11)

    _, spans = _record(body, tmp_path)
    outer, = [s for s in spans if s[0] == "boinc.test.outer"]
    inner, = [s for s in spans if s[0] == "boinc.test.inner"]
    assert outer[3] == {"shard": 3} and inner[3] == {"seq": 11}
    assert _inside(inner, outer)


def test_without_jax_a_span_does_nothing(monkeypatch):
    monkeypatch.setattr(jax_backend, "HAVE_JAX", False)
    sp = tracing.span("boinc.test.none", shard=1)
    with sp as entered:
        entered.set_metadata(seq=1)
    with pytest.raises(KeyError):
        with tracing.span("boinc.test.none"):
            raise KeyError("propagates")


def test_one_compile_counter_counts_a_new_shape():
    counter = tracing.CompileCounter.get()
    assert tracing.CompileCounter.get() is counter
    c0 = counter.snapshot()
    jax.jit(lambda x: x * 3 + 1)(np.arange(13.0)).block_until_ready()
    assert counter.snapshot()[0] > c0[0]
    path = Path(__file__).resolve().parent.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke_counter", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert chip_smoke.CompileCounter is tracing.CompileCounter
