"""Plain reference of JACOBI2D (arXiv:2208.10770, Listing 2): each cell
becomes the mean of itself and its four neighbours, with zeros outside
the grid.  The sum is taken in the order the paper writes it."""
import jax.numpy as jnp


def step(x):
    """One iteration over ``(..., rows, cols)`` grids, in ``x``'s dtype."""
    p = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)])
    return (p[..., 1:-1, 2:] + p[..., 2:, 1:-1] + p[..., 1:-1, 1:-1]
            + p[..., 1:-1, :-2] + p[..., :-2, 1:-1]) / 5
