"""The MH sampler kernel's share of its roofline in the training window:
the least time of the chain's algorithmic work (``work.mh_sample``) at the
chip's peaks over the kernel's time in the trace (%)."""

# The Pallas kernel of kernels/mh_sample.py as the trace names it today:
# the tpu_custom_call whose one output is the [1, B] int32 assignments.
KERNEL = r"^%[\w.\-]+ = s32\[1,\d+\]\{[^}]*\} custom-call\(.*tpu_custom_call"


def read(run):
    t = run.trace.kernel_seconds(KERNEL, "mh_sample (training)")
    if not t:
        return None
    return 100.0 * run.work["mh_sample"].least_seconds(run.peaks) / t
