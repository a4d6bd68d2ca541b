"""Work of dynamic routing by agreement (Sabour et al. 2017, Fig. 4) on
``u_hat`` of shape (B, J, D, I), ``iters`` iterations, the agreement step
skipped after the last.  Counts the algorithm's arithmetic and the bytes
a fused implementation must move (u_hat in, v and the couplings out,
float32), whatever implements it.

    softmax over J per (b, i):   5 J I     (max, subtract, exp, sum, divide)
    FC        s = sum_i c u:     2 J D I
    squash    per (b, j):        4 D + 4
    agreement b += <u, v>:       2 J D I   (iters - 1 times)
"""


def flops(b: int, j: int, d: int, i: int, iters: int) -> int:
    per = 5 * j * i + 2 * j * d * i + j * (4 * d + 4)
    return b * (iters * per + (iters - 1) * 2 * j * d * i)


def nbytes(b: int, j: int, d: int, i: int, itemsize: int = 4) -> int:
    return itemsize * b * (j * d * i + j * d + j * i)
