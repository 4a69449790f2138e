"""Forward GEMM kernel (``quant_matmul``, forward and its recomputation
under remat) in the traced slice: least time at the peaks over device
time."""
from readers import kernel_roofline


def read(ctx):
    tr = ctx["tr"]
    return kernel_roofline(ctx, "quant_matmul", tr["batch"] * tr["seq_len"],
                           1)
