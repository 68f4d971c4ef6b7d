"""device: 1 - busy / traced window, in percent."""


def read(ctx):
    r = ctx.get("reduced")
    if not r or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
