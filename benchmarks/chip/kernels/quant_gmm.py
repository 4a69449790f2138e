"""Operations and bytes the grouped expert kernel needs for one call
(``kernels/quant_matmul.py`` ``quant_gmm_pallas``): ``m`` routed rows of
width ``k``, each against the ``n`` output columns of its expert, with the
codes of the ``experts`` experts the rows touch read once each and, for
each of them, the scale and zero rows of the ``task_rows`` tasks the rows
use.  Untouched experts and the tiles' padding rows are not needed."""


def cost(m: int, n: int, k: int, bits: int, layout: str, groups: int,
         task_rows: float, experts: float, act_bytes: int = 2) -> tuple:
    code_bits = bits if layout == "plane" else 4    # nibbles hold 3 bits too
    flops = 2 * m * n * k
    nbytes = (experts * (n * k * code_bits // 8
                         + task_rows * n * groups * 4 * 2)
              + m * k * act_bytes + m * n * act_bytes)
    return flops, nbytes
