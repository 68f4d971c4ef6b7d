"""step program: union of the device-op intervals inside the traced
window, over the iterations traced."""


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    return 1e3 * r["busy_s"] / n
