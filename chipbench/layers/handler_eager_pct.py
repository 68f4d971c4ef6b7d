"""host loop: percent of the window's `handler/eval` spans whose `mode` is
`eager`: the task program fell back, permanently, to evaluating op by op
from the host. A count; it should be 0."""

from chipbench import loopspans


def read(ctx):
    return loopspans.attr_share_pct("handler/eval", "mode", "eager")
