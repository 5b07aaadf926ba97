"""Share of the measured window in which no operation ran on the chips,
averaged over them (device trace), in a train or prefill cell."""


def read(ctx):
    if ctx["counts"].get("kind") not in ("train", "prefill"):
        return None
    r = ctx["trace"]
    return 100.0 * (1.0 - r.busy_s / r.window_s)
