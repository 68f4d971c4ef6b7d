"""pencil solve, float64 route: share of the pencil products' roofline.
The least time is the bytes the pencil products of one Runge-Kutta step
have to read whatever implements them — 8 bytes a matrix entry, G S^2
entries a product, and as many products as the scheme asks for
(chipbench/costs_dd.py, from the `dd_shapes` the configuration's file
states and the run's G, S and stages) — over the chip's HBM bandwidth:
each matrix meets one column, so the layer is bound by bandwidth. Divided
by the measured self time of `dedalus/matsolve/dd.matvec` +
`dd.residual`. Only on a Runge-Kutta scheme."""

import json
import pathlib

from chipbench import costs_dd
from chipbench.tracered import scope_seconds

CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" \
    / "rb256x64-f64.json"


def read(ctx):
    r, n, facts = ctx.get("reduced"), ctx.get("iterations"), ctx.get("facts")
    if not r or not n or not facts or not facts.get("rk_stages"):
        return None
    measured = sum(scope_seconds(r, scope)
                   for scope in costs_dd.PRODUCT_SCOPES)
    if measured <= 0:
        return None
    cost = costs_dd.rk_dd_step(json.loads(CONFIG.read_text())["dd_shapes"],
                               facts["G"], facts["S"], facts["rk_stages"])
    least = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * n / measured
