"""entry: seconds of the configuration's `build` (problem text to a
factored solver), on the harness's clock around host work."""


def read(ctx):
    return ctx.get("build_s")
