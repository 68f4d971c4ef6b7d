"""host loop: time inside the traced window in which no operation ran on
the device, over the iterations traced."""


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    return 1e3 * (r["window_s"] - r["busy_s"]) / n
