"""A kernel's share of its roofline: the least time the chip could take for
the work the kernel had to do (the larger of its operations over the bf16
peak and its bytes over the HBM bandwidth) divided by the summed device
time of the kernel's events in the trace. Nothing where the trace holds no
such event or the kernel had no work."""


def share(run, kernel: str, flops: float, nbytes: float):
    if run.trace is None:
        return None
    spent = run.trace.kernel_seconds(kernel)
    if spent <= 0 or flops <= 0:
        return None
    t_flops = flops / run.peaks["bf16_flops"]
    t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    bound = "HBM bandwidth" if t_bytes >= t_flops else "bf16 peak"
    return 100.0 * max(t_flops, t_bytes) / spent, (
        f"bound by {bound}: {flops:.6g} operations, {nbytes:.6g} bytes, "
        f"{kernel} {spent:.6g} s")
