"""``chip_smoke.py``'s phases at tiny sizes on the CPU (Pallas in interpret
mode), and its refusal to run anywhere but on a TPU."""
import importlib.util
from pathlib import Path

import pytest

pytest.importorskip("jax")

_PATH = Path(__file__).resolve().parent.parent / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

_PHASES = {
    "world_tick": (chip_smoke.phase_world_tick, dict(n_hosts=2000, depth=4, ticks=3)),
    "emulator": (chip_smoke.phase_emulator, dict(n_hosts=200, horizon=chip_smoke.DAY / 64)),
    "served": (
        chip_smoke.phase_served,
        dict(n_hosts=64, n_jobs=600, cache_size=64, n_shards=4, n_requests=128, batch=32),
    ),
    "validation": (chip_smoke.phase_validation, dict(n_jobs=6, replicas=3, payload=3000)),
}


def _exact(result, *fields):
    for f in fields:
        assert result[f]["n_diff"] == 0, (f, result[f])


@pytest.mark.parametrize("name", list(_PHASES))
def test_phase_runs_tiny_on_cpu(name, capsys):
    fn, sizes = _PHASES[name]
    result = fn(**sizes)
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1, lines
    line = lines[0]
    assert line.startswith("[phase ") and name in line
    assert "(first chip reading, not a benchmark)" in line
    assert "compiles=" in line and "cache_hits=" in line
    for k, v in sizes.items():
        assert f'"{k}": {v}' in line
    # on XLA:CPU the f64 kernels are bit-identical to NumPy
    if name == "world_tick":
        assert result["touched_masks"] == result["completion_mask"] == "identical"
        assert result["completed_rows"] > 0
        assert result["donation_warnings"] == 0
        _exact(result, "q_runtime", "q_frac", "busy", "debits")
    elif name == "emulator":
        assert result["decisions"] == "identical"
        assert result["instances_executed"] > 0
        _exact(result, "busy_cpu_seconds", "credit_totals", "instance_runtime")
    elif name == "served":
        assert result["errors"] == 0 and result["replies"] == sizes["n_requests"]
        assert result["batch_assignments"] == "identical" and result["batch_jobs"] > 0
        _exact(result, "est_runtime")
    else:
        assert result["partitions"] == result["verdicts"] == "identical"
        assert result["jobs_valid"] > 0
        assert result["kernel"] == "interpret"  # compiled only off the CPU


def test_float_diff_counts_and_relative_error():
    n, rel = chip_smoke.float_diff([1.0, 2.0, float("nan"), 4.0], [1.0, 2.5, float("nan"), 4.0])
    assert n == 1 and rel == pytest.approx(0.2)
    assert chip_smoke.float_diff([0.0], [0.0]) == (0, 0.0)


def test_main_refuses_cpu_before_any_phase(monkeypatch, capsys):
    def boom(**kw):
        raise AssertionError("a phase ran")

    for name in ("phase_world_tick", "phase_emulator", "phase_served", "phase_validation"):
        monkeypatch.setattr(chip_smoke, name, boom)
    monkeypatch.setattr(chip_smoke.jax_backend, "configure_compile_cache", boom)
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "'cpu'" in out.err
