"""The whole step's share of the chips' peak: operations the steps of
the traced window require (``bench/work.py``: no remat, no padding, no
embedding lookup) over the window's length times the chips' bf16 peak."""


def read(ctx):
    c = ctx["counts"]
    if c.get("kind") not in ("train", "prefill") or not c.get("steps"):
        return None
    r = ctx["trace"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * c["step_flops"] * c["steps"] / (r.window_s * peak)
