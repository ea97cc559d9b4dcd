"""Operations and bytes that the selection algorithm needs, from shapes.

These count the work Algorithm 3 requires, not what today's program does:
the dense one-hot vote scatter (2 B k N) and ``votes @ path_weights``
(2 B N P) are left out, since neither is needed work, so a later program
that drops them reads as the gain it is.
"""
from __future__ import annotations


def select_pass_flops(rows: int, d_in: int, hidden: int, n_layers: int,
                      n_log: int, n_sets: int, knn: int) -> float:
    """One selection pass over ``rows`` real (unpadded) queries: the DSQE
    projection, the similarity to every log row, the prototype cosines and
    the k-neighbour vote."""
    widths = [d_in] + [hidden] * n_layers
    proj = sum(2 * rows * a * b for a, b in zip(widths, widths[1:]))
    return float(proj + 2 * rows * n_log * hidden
                 + 2 * rows * n_sets * hidden + 2 * rows * knn)


def retrieve_flops(bq: int, n: int, d: int) -> float:
    """The retrieve kernel's similarity GEMM: ``bq`` queries as called."""
    return float(2 * bq * n * d)


def retrieve_bytes(bq: int, n: int, d: int, k: int,
                   itemsize: int = 4, id_size: int = 4) -> float:
    """The retrieve kernel's own arguments and outputs, in the dtypes it is
    called with: queries and corpus in, top-k values and ids out."""
    return float((bq * d + n * d) * itemsize + bq * k * (itemsize + id_size))


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
