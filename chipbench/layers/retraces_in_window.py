"""step program: `tools.retrace.sentinel.post_arm_retraces` after the
window minus before it. A count; it should be 0."""


def read(ctx):
    return ctx.get("retraces_in_window")
