"""transpose: self time of the ops under `dedalus/transpose/` — the
all_to_all exchanges of the pencil transposes between x-sharded
coefficient and y-sharded grid layouts on a pencil mesh, and the chunk
slicing of their overlapped stages (parallel/transposes.py) — averaged over
the device planes (one per chip) and over the iterations traced. A run on
one chip has no transpose and no reading."""

from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    seconds = scope_seconds(r, "dedalus/transpose/")
    return 1e3 * seconds / n if seconds > 0 else None
