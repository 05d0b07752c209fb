"""The alias-build kernel's share of its roofline in the training window:
the least time of Vose's build over every vocabulary row once per sweep
(``work.alias_build``) at the chip's peaks over the kernel's time in the
trace (%)."""

# The Pallas kernel of kernels/alias_build.py as the trace names it today:
# the tpu_custom_call whose outputs are the [R, Kp] f32 probabilities and
# int32 aliases.
KERNEL = (r"^%[\w.\-]+ = \(f32\[\d+,\d+\]\{[^}]*\}, "
          r"s32\[\d+,\d+\]\{[^}]*\}\) custom-call\(.*tpu_custom_call")


def read(run):
    t = run.trace.kernel_seconds(KERNEL, "alias_build (training)")
    if not t:
        return None
    return 100.0 * run.work["alias_build"].least_seconds(run.peaks) / t
