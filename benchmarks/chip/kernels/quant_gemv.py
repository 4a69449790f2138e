"""Operations and bytes the decode-shaped quantized GEMV needs for one call
(``kernels/quant_matmul.py`` ``quant_gemv_pallas``): ``m`` activation rows
of width ``k`` against ``n`` output columns of ``bits``-bit codes, with the
scale and zero rows of ``task_rows`` tasks read (the tasks the rows use;
a stacked call's other rows are not needed).  The count is the GEMM's."""
import roofline

cost = roofline.kernel("quant_matmul").cost
