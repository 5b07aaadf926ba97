"""The RMSNorm kernel's share of its roofline in a prefill cell (see
``_roofline.py``)."""
from bench.metrics._roofline import share

#: the program's jitted wrapper of the kernel's ``pallas_call``
KERNEL = "rmsnorm_2d"


def read(ctx):
    if ctx["counts"].get("kind") != "prefill":
        return None
    return share(ctx, KERNEL, "rmsnorm")
