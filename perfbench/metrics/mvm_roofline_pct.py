"""Share of the roofline reached by the fused latent-Kronecker MVM kernel
in the traced window: the least time the chip could take for the calls'
work (``perfbench/roofline.py``), over the summed device time of the
kernel's events. The work is reckoned from (B, n, m), whatever implements
the MVM."""
from perfbench import roofline
from perfbench.trace import kernel_calls

KERNEL = r"lk_mvm_fused\S* = .*tpu_custom_call"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = kernel_calls(ctx.trace, KERNEL)
    if not calls:
        return None
    n, m = ctx.config["n"], ctx.config["m"]
    least = sum(roofline.least_seconds(roofline.lk_mvm_flops(B, n, m),
                                       roofline.lk_mvm_bytes(B, n, m),
                                       ctx.device_kind)[0]
                for _, B in calls)
    spent = sum(s for s, _ in calls)
    return 100.0 * least / spent if spent > 0 else None
