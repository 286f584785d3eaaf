"""int8_matmul: least time from shapes over its device time."""
from bench import readers


def read(run):
    return readers.kernel_roofline(run, "int8_matmul")
