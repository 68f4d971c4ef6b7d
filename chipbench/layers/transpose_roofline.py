"""transpose: share of its roofline. The least time is the bytes each chip
has to send in one Runge-Kutta step's all_to_all exchanges
(chipbench/costs_transpose.py, from rb3d128x128x128.json's sizes and
transposes and the run's chip count) over the chip's interchip interconnect
bandwidth (Google Cloud's "TPU v5e" documentation, in costs_transpose.py),
divided by the measured `transpose_ms_per_step`."""

import json
import pathlib

from chipbench import costs_transpose
from chipbench.tracered import scope_seconds

CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" \
    / "rb3d128x128x128.json"


def read(ctx):
    r, n, facts = ctx.get("reduced"), ctx.get("iterations"), ctx.get("facts")
    if not r or not n or not facts or not facts.get("rk_stages"):
        return None
    chips = len(r.get("planes") or [])
    measured = scope_seconds(r, "dedalus/transpose/")
    if chips < 2 or measured <= 0:
        return None
    cost = costs_transpose.rk_step(json.loads(CONFIG.read_text()), chips,
                                   facts["rk_stages"], facts["itemsize"])
    least = cost["bytes"] / costs_transpose.ICI_BYTES_PER_S
    return 100.0 * least * n / measured
