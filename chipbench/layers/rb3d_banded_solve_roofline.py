"""pencil solve, banded, of rb3d128x128x128: share of its roofline.
`banded_solve_roofline`'s own reader (chipbench/costs_banded.py: M's bands
once, L's bands and pinned rows once per stage, the packed factors and the
Woodbury blocks once per stage solve, over the chip's HBM bandwidth,
divided by the self time of `dedalus/matsolve/banded.*` less
`banded.factor`) on THIS configuration's `banded_shapes` (q = 80), with G
the groups of ONE chip: the pencils are sharded over the device planes of
the trace and the measured time is each chip's (the trace's scopes are
averaged over its planes). Loaded and pointed at rb3d128x128x128.json as
sw_banded_solve_roofline.py does: the same arithmetic, written once."""

import pathlib

from chipbench.manifest import load_module

_reader = load_module(pathlib.Path(__file__).with_name(
    "banded_solve_roofline.py"))
_reader.CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" \
    / "rb3d128x128x128.json"


def read(ctx):
    r, facts = ctx.get("reduced"), ctx.get("facts")
    if not r or not facts:
        return None
    planes = max(len(r.get("planes") or []), 1)
    return _reader.read(dict(ctx, facts=dict(facts,
                                            G=facts["G"] // planes)))
