"""A kernel's share of its roofline, shared by the
``<kernel>_roofline.<cells>`` readers.

The least time the chip could take for the work of the kernel's
operation in the window is the larger of the required operations over
the bf16 peak and the required bytes over the HBM bandwidth (the
operation's entries in the step's inventory, ``bench/work.py``, times
the window's steps); the share is that over the device time of the
kernel's calls in the trace, on one chip.  Calls the algorithm does not
need add time and no work.  A kernel's custom call is named after the
program's jitted wrapper of its ``pallas_call``
(``%flash_attention_bhsd.6``): each reader names the wrapper and the
operations of the inventory that the kernel does.
"""
from bench import work


def share(ctx, kernel: str, *ops: str):
    """``kernel``'s share of the roofline of the work of the operations
    ``ops``; None where the step has none of them or the trace no call
    of the kernel."""
    w = work.op_work(ctx["counts"].get("ops", []), *ops)
    if not w:
        return None
    calls, seconds = ctx["trace"].kernel(kernel)
    if not calls:
        return None
    p = ctx["peaks"]
    least = ctx["counts"]["steps"] * max(w["flops"] / p["bf16_flops_per_s"],
                                         w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
