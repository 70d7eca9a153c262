"""Share of the conv composition kernel's HBM roofline: the bytes each
``gridax.conv_cycles`` call of the window must move (``kernel_bytes``,
from the operand shapes the harness records for the calls this file
names in ``CALLS``) over the chip's peak HBM bandwidth
(``bench/peaks.json``), set against the device time that the trace
gives the program's ``repro.dse.compose_device`` spans, %.  Its
arithmetic is int64, for which the peak table holds no rate, so this is
the bytes side of the roofline alone."""
from math import prod

SPAN = "repro.dse.compose_device"
KERNEL = ("repro.core.gridax", "conv_cycles")
CALLS = (KERNEL,)
INT64 = 8


def kernel_bytes(div, occ, bw, w) -> int:
    """Bytes one ``conv_cycles(div, occ, bw, w)`` call must move, from the
    (shape, bytes per element) of its arguments: ``div`` [6 x T x U],
    ``occ`` [4 x T x U] and ``w`` [G x U] as passed, the int64 divisor
    constants [3 x 3 x B] it makes of ``bw`` [3 x B], and its int64
    result [G x T x B]."""
    (div_shape, div_b), (occ_shape, occ_b), (bw_shape, _), (w_shape, w_b) = \
        div, occ, bw, w
    n_t, n_b, n_g = div_shape[1], bw_shape[1], w_shape[0]
    return (prod(div_shape) * div_b + prod(occ_shape) * occ_b
            + 3 * prod(bw_shape) * INT64 + prod(w_shape) * w_b
            + n_g * n_t * n_b * INT64)


def read(run):
    d, rec = run.device, run.recorder
    if d is None or rec is None or d.op_s_by_span.get(SPAN, 0.0) <= 0:
        return None
    moved = sum(kernel_bytes(*ops) for t0, _t1, ops
                in rec.calls.get(KERNEL, ())
                if run.t_start <= t0 < run.t_end)
    if not moved:
        return None
    least_s = moved / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / d.op_s_by_span[SPAN]
