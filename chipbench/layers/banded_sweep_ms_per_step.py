"""pencil solve, banded: self time of the two block-row scans of the
substitution, `dedalus/matsolve/banded.fwd` and `banded.bwd`, over the
iterations traced: the part of the solve that is a sequence (block rows x
chunks x sweeps x stages x (1 + refinement sweeps) scan bodies a step)
rather than a stream."""

from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    sweeps = scope_seconds(r, "dedalus/matsolve/banded.fwd") \
        + scope_seconds(r, "dedalus/matsolve/banded.bwd")
    return 1e3 * sweeps / n if sweeps > 0 else None
