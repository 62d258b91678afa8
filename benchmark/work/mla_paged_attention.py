"""Operations and bytes that latent (MLA) attention over latent pages
needs for the rows the traffic really sent, whatever form the kernel
takes (absorbed or expanded) and whatever grid it pads them into.

``shape["rows"]`` holds one entry per (tick, request): [pos0, n] = n query
tokens at positions pos0 .. pos0+n-1.  A query at position p attends p + 1
keys: 2 heads (qk_dim + v_dim) flops a query-key pair, the model's own
count.  The request's latent rows (``latent`` values a token) up to its
last query are read once per tick and layer; its queries (heads x qk_dim)
are read and its outputs (heads x v_dim) written once."""


def work(shape: dict) -> tuple:
    heads, qk, v = shape["heads"], shape["qk_dim"], shape["v_dim"]
    latent, layers = shape["latent"], shape["layers"]
    item = shape.get("itemsize", 2)
    flops = nbytes = 0.0
    for pos0, n in shape["rows"]:
        keys = n * pos0 + n * (n + 1) / 2.0          # sum of (p + 1)
        flops += 2.0 * heads * (qk + v) * keys
        nbytes += item * (latent * (pos0 + n) + heads * (qk + v) * n)
    return flops * layers, nbytes * layers
