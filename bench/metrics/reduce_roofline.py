"""Share of the device reduction's roofline: every candidate the device
calls of the window return is one int64 written (8 bytes); the least
time for that is its bytes over the chip's peak HBM bandwidth
(``bench/peaks.json``), set against the device time that the trace
gives the program's ``repro.dse.reduce`` spans, %."""

BYTES_PER_CANDIDATE = 8
SPAN = "repro.dse.reduce"


def read(run):
    d, rec = run.device, run.recorder
    if d is None or rec is None or d.op_s_by_span.get(SPAN, 0.0) <= 0:
        return None
    cands = sum(c for t0, _t1, c in rec.device_calls
                if run.t_start <= t0 < run.t_end)
    if not cands:
        return None
    least_s = cands * BYTES_PER_CANDIDATE / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / d.op_s_by_span[SPAN]
