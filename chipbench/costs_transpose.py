"""
Bytes of the pencil transposes from shapes: the numerator of
`transpose_roofline`. Computed, not measured; what the algorithm has to
move, not what a program happens to move.

On a 1-D mesh of n chips a 3-D field is x-sharded in coefficient space and
y-sharded in grid space (core/field.py's walk): every backward transform of
a component transposes once, after z and y are in grid space and before x
leaves coefficient space, and every forward transform once, after x is in
coefficient space. Either way a chip holds Nx * Gy * Gz / n numbers at the
exchange (G = the dealiased grid size) and sends (n - 1)/n of them. Each
exchange is `chunks` all_to_all ops (parallel/transposes.py, `[distributed]
TRANSPOSE_CHUNKS`: 4 on an accelerator), fewer where the chunk count does
not divide the destination block (`stage_chunks`).

Which components a right-hand side transforms is the configuration's
(`transposes` in its file): for Rayleigh-Benard's `- u@grad(b)` and
`- u@grad(u)`, u (3), grad(b) (3) and grad(u) (9) backward, u@grad(b) (1)
and u@grad(u) (3) forward: 19 a stage, 38 an RK222 step.
"""

# Google Cloud documentation, "TPU v5e" (system architecture): interchip
# interconnect bandwidth 1,600 Gbps a chip (peaks.json has no ICI entry)
ICI_BYTES_PER_S = 1600e9 / 8


def stage_chunks(requested, block):
    """parallel/transposes.py's rule: the largest count <= `requested`
    that divides the destination block."""
    c = max(1, min(int(requested), int(block)))
    while block % c:
        c -= 1
    return c


def rk_step(spec, chips, stages, itemsize, chunks=4):
    """{ops, bytes}: the all_to_all ops of one Runge-Kutta step and the
    bytes each chip sends in them, from the configuration's `sizes` and
    `transposes`."""
    s, t = spec["sizes"], spec["transposes"]
    Nx, Ny, Nz = int(s["Nx"]), int(s["Ny"]), int(s["Nz"])
    Gy, Gz = int(Ny * s["dealias"]), int(Nz * s["dealias"])
    n = int(chips)
    held = Nx * Gy * Gz // n                  # a component, at the exchange
    components = sum(t["backward"]) + sum(t["forward"])
    ops = (len(t["backward"]) * stage_chunks(chunks, Gy // n)
           + len(t["forward"]) * stage_chunks(chunks, Nx // n))
    return {"ops": stages * ops,
            "bytes": stages * components * held * (n - 1) // n * itemsize}
