"""Operations and bytes that causal self-attention needs, forward and
backward, for ``steps`` training steps on one chip: from (b, heads, s, d) and the
number of layers, whatever kernel computes it.

Forward: QK^T and PV, 2 matmuls of 2 b h s s d flops, half of it under the
causal mask.  Backward: dV, dP, dQ, dK, 4 such matmuls; the recomputation
of QK^T that a flash backward makes is not counted.  Bytes: q, k, v read
and o written forward; q, k, v, o, do read and dq, dk, dv written
backward; bf16."""


def work(shape: dict) -> tuple:
    b, h, s, d = shape["b"], shape["heads"], shape["s"], shape["d"]
    layers = shape.get("layers", 1) * shape.get("steps", 1)
    one_matmul = 2.0 * b * h * s * s * d / 2.0          # causal half
    flops = (2 + 4) * one_matmul * layers
    tensor = b * h * s * d * shape.get("itemsize", 2)
    nbytes = (4 + 8) * tensor * layers
    return flops, nbytes
