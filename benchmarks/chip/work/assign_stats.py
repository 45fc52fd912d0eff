"""Work of one ``assign_stats`` call over (rows, d) rows and k centers.

FLOPs: 2·rows·k·d for the similarities, rows·d adds into the members'
cluster sums, and 2·rows·d for the squared row norms. Bytes: the rows read
once, the centers read once, and the outputs written once (index and best
similarity per row; sums, counts, least similarity and squared norms per
cluster). Logical shapes only: padding a kernel adds is not work the
algorithm needs.
"""


def work(*, rows: int, k: int, d: int, itemsize: int = 4) -> tuple[float, float]:
    flops = 2.0 * rows * k * d + 3.0 * rows * d
    nbytes = itemsize * (rows * d + k * d + 2 * rows + k * d + 3 * k)
    return flops, float(nbytes)
