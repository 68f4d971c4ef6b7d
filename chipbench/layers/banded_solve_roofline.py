"""pencil solve, banded: share of its roofline. The least time is the
bytes one Runge-Kutta step has to read — M's bands once, L's bands and
pinned rows once per stage, the packed factors and the Woodbury blocks once
per stage solve (chipbench/costs_banded.py, from the shapes the
configuration's file states and the run's G and S; a refinement sweep is
the implementation's choice and is not counted) — over the chip's HBM
bandwidth: the layer is bound by bandwidth. Divided by the measured self
time of the `dedalus/matsolve/banded.*` ops less `banded.factor`. Only for
banded pencils on a Runge-Kutta scheme."""

import json
import pathlib

from chipbench import costs_banded

CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" \
    / "rb2048x1024.json"


def read(ctx):
    r, n, facts = ctx.get("reduced"), ctx.get("iterations"), ctx.get("facts")
    if not r or not n or not facts or facts["ops"] != "BandedOps" \
            or not facts.get("rk_stages"):
        return None
    measured = sum(v for k, v in r["scopes"].items()
                   if "dedalus/matsolve/banded." in k
                   and not k.endswith(".factor"))
    if measured <= 0:
        return None
    shape = costs_banded.shapes(json.loads(CONFIG.read_text()),
                                facts["G"], facts["S"])
    cost = costs_banded.rk_banded_step(shape, facts["rk_stages"],
                                       facts["itemsize"])
    least = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * n / measured
