"""Operations and bytes that attention over a paged KV cache needs for the
rows the traffic really sent, whatever grid the kernel pads them into.

``shape["rows"]`` holds one entry per (tick, request): [pos0, n] = n query
tokens at positions pos0 .. pos0+n-1 (a decode row is n = 1, a prefill
slice up to the prompt's rest).  A query at position p attends p + 1 keys:
4 heads d (p + 1) flops for QK^T and PV.  The request's keys and values up
to its last query are read once per tick and layer, its queries read and
outputs written once."""


def work(shape: dict) -> tuple:
    heads, kv, d = shape["heads"], shape["kv_heads"], shape["d"]
    layers, item = shape["layers"], shape.get("itemsize", 2)
    flops = nbytes = 0.0
    for pos0, n in shape["rows"]:
        keys = n * pos0 + n * (n + 1) / 2.0          # sum of (p + 1)
        flops += 4.0 * heads * d * keys
        nbytes += item * (2 * kv * d * (pos0 + n) + 2 * heads * d * n)
    return flops * layers, nbytes * layers
