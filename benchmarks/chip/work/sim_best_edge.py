"""Work of one ``sim_best_edge`` call: for each of ``rows`` rows, the most
similar of ``cols`` columns in another component, all of width d.

FLOPs: 2·rows·cols·d for the similarities (the masking and the running
arg-max are compares). Bytes: both operands and both label vectors read
once, and a column index and a similarity written per row.
"""


def work(*, rows: int, cols: int, d: int, itemsize: int = 4) -> tuple[float, float]:
    flops = 2.0 * rows * cols * d
    nbytes = itemsize * ((rows + cols) * d + (rows + cols) + 2 * rows)
    return flops, float(nbytes)
