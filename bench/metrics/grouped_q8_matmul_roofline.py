"""Kernels: grouped_q8_matmul's share of its roofline in the traced
window: the least time of each call (the larger of its operations over
the bf16 peak and its bytes over HBM bandwidth, from the call's shapes;
``bench/kernels/grouped_matmul.py``), summed, over the summed device time
of the kernel's events, in %."""


def read(ctx):
    tr = ctx["trace"]
    k = (tr or {}).get("kernels", {}).get("grouped_q8_matmul")
    if not k or k["device_s"] <= 0:
        return None
    return 100.0 * k["least_s"] / k["device_s"]
