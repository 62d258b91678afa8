"""Operations and bytes that paged attention with keys wider than values
needs for the rows the traffic really sent, per cache class and layer,
whatever grid the kernel pads them into.

``shape["classes"]`` holds, per class, ``layers``, ``heads``,
``kv_heads``, ``dk`` (q and k), ``dv`` (v and the output), ``window``
(None: every earlier key) and ``rows``: one ``[pos0, n]`` per (tick,
request), n query tokens at positions pos0 .. pos0+n-1.  A query at
position p attends ``p + 1`` keys, ``min(p + 1, window)`` under a window:
2 heads (dk + dv) operations a query-key pair.  The keys and values a
row's queries can see (every one up to its last query, or from the first
query's window on) are read once per tick and layer at kv_heads (dk + dv)
values a token; its queries (heads x dk) are read and its outputs (heads
x dv) written once."""


def _pairs(pos0: int, n: int, window) -> float:
    """Sum over p in [pos0, pos0 + n) of the keys the query at p sees."""
    full = n * pos0 + n * (n + 1) / 2.0
    if window is None:
        return full
    under = max(0, min(pos0 + n, window - 1) - pos0)  # queries p < W - 1
    return (under * pos0 + under * (under + 1) / 2.0
            + (n - under) * float(window))


def work(shape: dict) -> tuple:
    flops = nbytes = 0.0
    for c in shape["classes"]:
        heads, kv, dk, dv = c["heads"], c["kv_heads"], c["dk"], c["dv"]
        window, item = c["window"], c.get("itemsize", 2)
        f = b = 0.0
        for pos0, n in c["rows"]:
            f += 2.0 * heads * (dk + dv) * _pairs(pos0, n, window)
            keys = pos0 + n if window is None else (
                pos0 + n - max(0, pos0 - window + 1))
            b += item * (kv * (dk + dv) * keys + heads * (dk + dv) * n)
        flops += f * c["layers"]
        nbytes += b * c["layers"]
    return flops, nbytes
