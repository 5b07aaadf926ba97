"""Flash attention's share of its roofline in a prefill cell (see
``_roofline.py``)."""
from bench.metrics._roofline import share

#: the program's jitted wrapper of the kernel's ``pallas_call``
KERNEL = "flash_attention_bhsd"


def read(ctx):
    if ctx["counts"].get("kind") != "prefill":
        return None
    return share(ctx, KERNEL, "attention")
