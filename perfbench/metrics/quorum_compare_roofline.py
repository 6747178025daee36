"""Share of the HBM roofline in the validation passes, %.

The least time the chip could take is the bytes the passes must read at
least (every reported result's payload once: results x elements x 4
bytes, fixed by the payload shapes, whatever kernel does the comparing)
over the chip's published HBM bandwidth. It is divided by the time in
which any device operation ran inside the window's ``validate_pass``
spans, so the casts, pads and reshapes around the comparison kernel
count as its time too."""
from perfbench.harness import device, readings, trace


def read(run):
    if run.trace is None:
        return None
    spans = readings.window_spans(run, "validate_pass")
    lo, hi = run.window
    least = sum(b for (t0, _, _, _), b in zip(run.data["passes"], run.data["least_bytes"])
                if lo <= t0 < hi)
    ops = readings.ops(run)
    busy = sum(trace.busy(ops, a, b) for _, a, b in spans)
    if not spans or busy <= 0:
        return None
    floor_s = least / device.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (busy / 1e9)
