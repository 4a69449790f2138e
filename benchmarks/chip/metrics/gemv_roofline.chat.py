"""Task-stacked GEMV kernel (``quant_gemv``) in the decode steps of the
traced slice: least time at the peaks over device time."""
from readers import kernel_roofline, mean_task_rows


def read(ctx):
    return kernel_roofline(ctx, "quant_gemv", ctx["tr"]["pool"]["slots"],
                           mean_task_rows(ctx), span="serve.decode")
