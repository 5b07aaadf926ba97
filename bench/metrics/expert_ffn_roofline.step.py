"""The experts' products' share of their roofline in a prefill cell: the
least time the chip could take for the inventory's ``expert_ffn`` work
of a step (the larger of its operations over the bf16 peak and its
bytes over the HBM bandwidth, ``bench/families/moe.py``) over their
device time per step on one chip (``_expert_ffn.py``)."""
from bench import work
from bench.metrics._expert_ffn import seconds_per_step


def read(ctx):
    if ctx["counts"].get("kind") != "prefill":
        return None
    w = work.op_work(ctx["counts"].get("ops", []), "expert_ffn")
    s = seconds_per_step(ctx)
    if not w or not s:
        return None
    p = ctx["peaks"]
    least = max(w["flops"] / p["bf16_flops_per_s"],
                w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / s
