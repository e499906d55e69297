"""Work counts of a stencil run, from the configuration's published
stencil (not from the program's IR), so a roofline counts the same work
whatever implements it."""
from __future__ import annotations

import math


def cells(shape) -> int:
    return math.prod(int(n) for n in shape)


def stencil_ops(config: dict, shape, iterations: int, grids: int) -> int:
    """Operations of ``grids`` runs of ``iterations`` iterations over
    ``shape``: the published stencil's operations per cell (as written,
    e.g. JACOBI2D's 4 adds and 1 divide) for every cell and iteration."""
    return int(config["stencil"]["ops_per_cell"]) * cells(shape) * iterations * grids


def stencil_bytes(config: dict, shape, grids: int) -> int:
    """Least HBM traffic of ``grids`` dispatched runs over ``shape``: one
    read of every input and one write of the output, whatever the number
    of iterations fused in between."""
    st = config["stencil"]
    per_cell = (int(st["inputs"]) + 1) * int(st["itemsize"])
    return per_cell * cells(shape) * grids
