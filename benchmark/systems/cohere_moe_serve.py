"""System under test for serving cells of the window-and-global,
routed-expert family: ``ServingEngine`` over ``models/cohere_moe.py`` at the
configuration's widths, holding the experts and the vocabulary slice the
configuration's deployment gives this chip, with the engine geometry the
configuration file states (``engine.n_pages`` is the global cache class's
pool, ``engine.class_pages`` the further classes').  The interface is
``llama_serve.py``'s: the engine's jitted step keeps its first eleven
operands, so its recording of the row tables and ``memory_analysis`` are
taken from there."""

from __future__ import annotations

import jax

from benchmark import harness

_llama = harness.load_module("systems/llama_serve.py")


def masked_rows(rows: list, window: int) -> list:
    """The recorded rows as ``work/ragged_paged_attention.py`` must see
    them for ONE window layer.  That function counts, for a row ``[pos0,
    n]``, ``n * pos0 + n (n + 1) / 2`` query-key pairs: ``p + 1`` keys for
    the query at ``p``.  Under a window a query sees ``min(p + 1, W)``:

    - queries at ``p < W - 1`` see ``p + 1``: that part of the row stays
      ``[pos0, n1]``;
    - the ``n2`` queries at ``p >= W - 1`` see ``W`` each, and ``[W - (n2
      + 1) / 2, n2]`` counts exactly ``n2 * W`` under the function's
      formula: ``n2 (W - (n2 + 1) / 2) + n2 (n2 + 1) / 2``.

    A row that straddles ``W - 1`` is split in two.  The function's bytes
    then read ``W + (n2 - 1) / 2`` keys where the layer reads ``W + n2 -
    1``: ``(n2 - 1) / 2`` short, exact for a decode row (``n2 = 1``), and a
    chunk is bound by its operations."""
    out = []
    for pos0, n in rows:
        n1 = min(n, max(0, window - 1 - pos0))
        if n1:
            out.append([pos0, n1])
        if n - n1:
            out.append([window - (n - n1 + 1) / 2.0, n - n1])
    return out


class ServeSystem(_llama.ServeSystem):
    def __init__(self, config: dict, devices, ref, key):
        from paddle_tpu.inference.serving import Request, ServingEngine
        from paddle_tpu.models.cohere_moe import CohereMoeConfig

        m, e = config["model"], config["engine"]
        self.model, self.geometry, self.Request = m, e, Request
        self.cfg = CohereMoeConfig.from_hf(
            m, n_routed_experts=m["num_experts_published"],
            held=tuple(m["held_experts"]), max_seq_len=e["max_seq"])
        with jax.default_device(devices[0]):
            params = jax.jit(lambda k: ref.make_params(m, k))(key)
            self.engine = ServingEngine(
                self.cfg, params=params, max_batch=e["max_batch"],
                page_size=e["page_size"], max_seq=e["max_seq"],
                n_pages=e["n_pages"], class_pages=e.get("class_pages"),
                prefill_budget=e["prefill_budget"],
                prefix_cache=e["prefix_cache"], qb=e["qb"])

    def counters(self) -> dict:
        """The engine's counters, its experts' and its cache classes'
        sums, and ``kv_live_centibytes``: the bytes of the pages live
        requests held, summed over ticks and classes, in hundredths, so
        that ``counter_ratio`` (a percentage) over ``context_tokens_live``
        reads bytes a context token over exactly the window."""
        eng, out = self.engine, super().counters()
        st = eng.stats
        out.update({k: st[k] for k in eng.model.stats_keys})
        out.update({k: st[k] for k in (
            "context_tokens_live", "pages_released_by_window",
            "prefill_window_lost_tokens")})
        out["kv_live_centibytes"] = sum(
            st[f"pages_live.{c.name}"] * eng.kv_bytes_per_page(i)
            for i, c in enumerate(eng.classes)) / 100.0
        for c in eng.classes:
            out[f"pages_live.{c.name}"] = st[f"pages_live.{c.name}"]
        return out

    def matmul_flops_per_token(self) -> float:
        """2 x the matrix weights a token passes through in the layers:
        attention's four projections, the four shared experts, the router
        and the routed experts the token really met HERE (from the
        engine's assignment counters: about one of its eight, the rest
        are held elsewhere)."""
        c, st = self.cfg, self.engine.stats
        H, q = c.hidden, c.n_heads * c.head_dim
        kv = c.n_kv_heads * c.head_dim
        expert = 3 * H * c.expert_hidden
        met = (c.experts_per_token * st["moe_assigned_held"]
               / max(1, st["moe_assigned_all"]))
        return 2.0 * c.n_layers * (
            H * (q + 2 * kv) + q * H + H * c.n_routed_experts
            + (c.n_shared_experts + met) * expert)

    def attention_shape(self, rows: list) -> dict:
        """``serve_common.run`` builds ``serve_step_mfu`` and the kernel's
        roofline from ``work/ragged_paged_attention.py`` on this shape,
        and that function counts ``p + 1`` keys a query in every layer.
        So it gets ``layers: 1`` and each recorded row once per layer with
        what that layer's mask admits: as recorded for a global layer,
        through ``masked_rows`` for a window layer.  The work counted is
        then the masks' own, whatever implements them."""
        c = self.cfg
        kinds = c.kinds
        return {"heads": c.n_heads, "kv_heads": c.n_kv_heads,
                "d": c.head_dim, "layers": 1,
                "rows": (kinds.count("global") * [list(r) for r in rows]
                         + kinds.count("window")
                         * masked_rows(rows, c.sliding_window))}

    def kv_pool_shapes(self) -> list:
        """Both cache classes' pools, whole or one layer's slice."""
        eng = self.engine
        pools = [eng.k_pages, eng.v_pages] + [
            a for x in eng._extra for a in (x.k_pages, x.v_pages)]
        return [list(s) for a in pools for s in (
            a.shape, (1,) + a.shape[1:], a.shape[1:])]

    def free(self) -> None:
        for x in self.engine._extra:
            x.k_pages = x.v_pages = None
        super().free()


def build(config: dict, devices, ref, key) -> ServeSystem:
    return ServeSystem(config, devices, ref, key)
