"""ditto_diff_matmul: least time (bytes moved, non-zero tiles) over its device time."""
from bench import readers


def read(run):
    return readers.kernel_roofline(run, "ditto_diff_matmul")
