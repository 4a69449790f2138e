"""Operations and bytes the quantized GEMM needs for one call
(``kernels/quant_matmul.py`` ``quant_matmul_pallas``): ``m`` rows of width
``k`` against ``n`` columns of ``bits``-bit codes, each code read once,
with ``task_rows`` tasks' scale and zero rows."""


def cost(m: int, n: int, k: int, bits: int, layout: str, groups: int,
         task_rows: int, act_bytes: int = 2) -> tuple:
    code_bits = bits if layout == "plane" else 4    # nibbles hold 3 bits too
    flops = 2 * m * n * k
    nbytes = (n * k * code_bits // 8
              + task_rows * n * groups * 4 * 2
              + m * k * act_bytes + m * n * act_bytes)
    return flops, nbytes
