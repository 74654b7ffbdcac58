"""Share of the roofline that the DAISM Pallas GEMM reaches.

Numerator: for each traced step, the least time of its weight GEMMs at the
live rows (``bench/flops.py:gemm_roofline_s``: per GEMM the larger of
operations over the bf16 peak and bytes over HBM bandwidth). Denominator:
device time of the Mosaic custom calls in the trace. The numerator is the
same work whatever implements it, so the share cannot pass 100% when the
kernel changes.
"""
from bench import flops, peaks, trace


def read(run):
    kernel_s = run["trace"]["kernel_s"]
    launches = trace.launches(run)
    if not kernel_s or not launches:
        return None
    pk = peaks.peaks(run["device_kind"])
    least = sum(flops.gemm_roofline_s(run["conf"]["model"], rows, pk)
                for _, rows in launches)
    return 100.0 * least / kernel_s
