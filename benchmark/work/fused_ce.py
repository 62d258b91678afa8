"""Operations and bytes of the softmax cross-entropy head for ``steps`` steps:
logits = x W forward, dx = dlogits W^T and dW = x^T dlogits backward, three
matmuls of 2 N H V flops (the logits' recomputation in the backward is not
counted).  Bytes: x and W read by each of the three, dx and dW written,
bf16; the logits never live in HBM."""


def work(shape: dict) -> tuple:
    n, h, v = shape["tokens"], shape["hidden"], shape["vocab"]
    item = shape.get("itemsize", 2)
    steps = shape.get("steps", 1)
    flops = 3 * 2.0 * n * h * v
    nbytes = item * (3 * (n * h + h * v) + n * h + h * v) + 4 * n
    return flops * steps, nbytes * steps
