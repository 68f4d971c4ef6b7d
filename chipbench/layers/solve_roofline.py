"""pencil solve: share of its roofline. The least time is the bytes the
step's matvecs and solves have to read (M once, then L and the stored
inverse once per stage; from shapes, chipbench/costs.py) over the chip's
HBM bandwidth — the solve is bound by bandwidth, its flops are a hundredth
of what the chip could do in that time — divided by the measured time of
the `dedalus/matsolve/{matvec,matvec_pair,solve}` ops. Factorizations are
left out of both sides. Only for dense pencils on a Runge-Kutta scheme;
another layout brings its own cost function and reader."""

from chipbench import costs


def read(ctx):
    r, n, facts = ctx.get("reduced"), ctx.get("iterations"), ctx.get("facts")
    if not r or not n or not facts or facts["ops"] != "DenseOps" \
            or not facts.get("rk_stages"):
        return None
    measured = sum(v for k, v in r["scopes"].items()
                   if "dedalus/matsolve/" in k and not k.endswith(".factor"))
    if measured <= 0:
        return None
    cost = costs.rk_dense_step(facts["G"], facts["S"], facts["rk_stages"],
                               facts["itemsize"])
    least, _ = costs.least_seconds(cost, ctx["peaks"])
    return 100.0 * least * n / measured
