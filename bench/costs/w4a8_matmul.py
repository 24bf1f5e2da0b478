"""``w4a8_matmul``: int8 MACs against the int8 peak, and bytes of each
operand and result once, with the shapes taken from the compiled
program's ``tpu_custom_call`` operands (so a change of M reads right)."""
from bench import roofline


def cost(operands: list, result: list) -> tuple[float, float]:
    """(int8 ops, bytes) of one call from its HLO operands: x (M, K),
    activation scale (M, 1), packed codes (K*bits/8, N), scale and zero
    point (K/g, N); result (M, N).  Every row of M is computed."""
    (_, (m, k)) = operands[0]
    n = operands[2][1][1]
    ops = 2.0 * m * k * n
    nb = sum(map(roofline.nbytes, operands)) + sum(map(roofline.nbytes,
                                                       result))
    return ops, float(nb)


def bound_s(ctx: dict, prog: str, events: list, pk: dict) -> float:
    calls = ctx["trace"]["calls"][prog]
    total = 0.0
    for name, _ in events:
        _, operands, result = calls[name]
        total += roofline.bound_s(*cost(operands, result), pk["int8_ops"],
                                  pk["hbm_bytes_s"])[0]
    return total
