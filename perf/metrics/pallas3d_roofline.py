"""The fused Pallas 3-D kernel's share of its roofline.

The window's fixed work (perf/work3d.py: 113 lane-ops per 32-cell word
per generation, and each job's packed volume read and written once) over
the kernel's device time in the trace, against the chip's peaks
(perf/peaks.py): the larger of the ops bound and the bytes bound, over
kernel time.  Kernel time is the mean over the cell's chips of the job
program's Mosaic custom calls, found by their target as
``pallas2d_roofline`` finds the 2-D kernel's.  Nothing is read where no
such op ran: a volume stepped by the XLA tiers has no kernel to share.
"""
from perf import work3d

MARKER = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    r, h = ctx.reduced, ctx.h
    if r is None or ctx.peaks is None or not h.jobs:
        return None
    kernel_s = sum(s for name, s in r["op_s"].items() if MARKER in name)
    if kernel_s <= 0:
        return None
    n = r["n_devices"]
    t_ops = sum(work3d.job_ops(j["cells"], j["generations"]) for j in h.jobs) / n / (
        ctx.peaks["vpu_lane_ops"]
    )
    t_bytes = sum(work3d.job_bytes(j["cells"]) for j in h.jobs) / n / (
        ctx.peaks["hbm_bytes_per_s"]
    )
    h.notes["pallas3d_roofline_bound"] = "ops" if t_ops >= t_bytes else "bytes"
    h.notes["pallas3d_kernel_s"] = kernel_s
    return 100.0 * max(t_ops, t_bytes) / kernel_s
