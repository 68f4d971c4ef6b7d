"""pencil solve, ensemble: share of the fleet products' roofline. The least
time of one fleet step's products (chipbench/costs_fleet.py: 2 N G S^2
operations a product, the stack once plus the N members' vectors in and
out, 1 + 2 stages products; N the configuration's member count, G, S, the
stages and the item size the run's), by `costs.least_seconds`' convention
against the chip's single-pass bf16 peak and its HBM bandwidth, over the
measured self time of `dense.fleet_matvec` + `dense.fleet_solve`. At
1,024 members a product does 105 flop a byte: under the v5e's one-pass
ridge (240), so the least time is the bytes' (3.47 GB a step, 4.2 ms); at
'highest' the MXU runs float32 as six bf16 passes (ridge 40), so what the
chip can reach is the operations' time six times over (11.1 ms a step),
and this share cannot pass about 38% on a v5e at this shape; it can never
pass 100%. Only on a Runge-Kutta scheme."""

import json
import pathlib

from chipbench import costs, costs_fleet
from chipbench.tracered import scope_seconds

CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" \
    / "rb256x64-ens.json"


def read(ctx):
    r, n, facts = ctx.get("reduced"), ctx.get("iterations"), ctx.get("facts")
    if not r or not n or not facts or not facts.get("rk_stages") \
            or not ctx.get("peaks"):
        return None
    measured = sum(scope_seconds(r, scope)
                   for scope in costs_fleet.PRODUCT_SCOPES)
    if measured <= 0:
        return None
    members = json.loads(CONFIG.read_text())["sizes"]["members"]
    cost = costs_fleet.rk_fleet_step(facts["G"], facts["S"],
                                     facts["rk_stages"], members,
                                     facts["itemsize"])
    least, _ = costs.least_seconds(cost, ctx["peaks"])
    return 100.0 * least * n / measured
