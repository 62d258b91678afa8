"""System under test for serving cells of the MiMo-V2 family:
``ServingEngine`` over ``models/mimo_v2.py`` at the configuration's
widths, holding the experts and the vocabulary slice the configuration's
deployment gives this chip, with the engine geometry the configuration
file states (``engine.n_pages`` is the global cache class's pool,
``engine.class_pages`` the window class's).  The interface, the counters
over both cache classes and the pools' shapes are
``cohere_moe_serve.py``'s: the two classes differ in their page shapes
(4 and 8 kv heads), and each counts at its own bytes a page."""

from __future__ import annotations

import jax

from benchmark import harness

_cohere = harness.load_module("systems/cohere_moe_serve.py")


class ServeSystem(_cohere.ServeSystem):
    def __init__(self, config: dict, devices, ref, key):
        from paddle_tpu.inference.serving import Request, ServingEngine
        from paddle_tpu.models.mimo_v2 import MimoV2Config

        m, e = config["model"], config["engine"]
        self.model, self.geometry, self.Request = m, e, Request
        self.cfg = MimoV2Config.from_hf(
            m, n_routed_experts=m["n_routed_experts_published"],
            held=tuple(m["held_experts"]), max_seq_len=e["max_seq"])
        with jax.default_device(devices[0]):
            params = jax.jit(lambda k: ref.make_params(m, k))(key)
            self.engine = ServingEngine(
                self.cfg, params=params, max_batch=e["max_batch"],
                page_size=e["page_size"], max_seq=e["max_seq"],
                n_pages=e["n_pages"], class_pages=e.get("class_pages"),
                prefill_budget=e["prefill_budget"],
                prefix_cache=e["prefix_cache"], qb=e["qb"])

    def matmul_flops_per_token(self) -> float:
        """2 x the matrix weights a token passes through in the layers:
        each layer's four projections at its own widths (q and k 192, v
        128, its kind's kv heads), the dense layer once, and in each expert
        layer the router and the routed experts the token really met HERE
        (from the engine's assignment counters: about one of its eight, the
        rest are held elsewhere)."""
        c, st = self.cfg, self.engine.stats
        H, nH, dk, dv = c.hidden, c.n_heads, c.head_dim, c.v_head_dim
        met = (c.experts_per_token * st["moe_assigned_held"]
               / max(1, st["moe_assigned_all"]))
        total = 0.0
        for kind, moe in zip(c.kinds, c.moe_layers):
            nKV = c.kv_heads(kind)
            total += H * (nH * dk + nKV * (dk + dv)) + nH * dv * H
            total += (H * c.n_routed_experts + met * 3 * H * c.expert_hidden
                      if moe else 3 * H * c.dense_hidden)
        return 2.0 * total

    def attention_shape(self, rows: list) -> dict:
        """``serve_common.run`` builds ``serve_step_mfu`` from
        ``work/ragged_paged_attention.py`` on this shape, which counts ``4
        heads d`` operations a query-key pair: with ``d`` = (192 + 128) / 2
        that is the model's 2 heads (dk + dv), and each recorded row goes
        in once per layer with what that layer's mask admits (as recorded
        for a full layer, through ``masked_rows`` for a window layer).
        ``classes`` is what ``work/split_width_paged_attention.py`` reads
        (the kernel's roofline): per cache class its layers, heads, kv
        heads, both widths, window and the rows as recorded."""
        c = self.cfg
        kinds = c.kinds
        classes = [
            {"name": name, "layers": kinds.count(kind), "heads": c.n_heads,
             "kv_heads": c.kv_heads(kind), "dk": c.head_dim,
             "dv": c.v_head_dim,
             "window": c.sliding_window if kind == "window" else None,
             "rows": [list(r) for r in rows]}
            for name, kind in (("global", "full"), ("window", "window"))
            if kind in kinds]
        return {"heads": c.n_heads, "kv_heads": c.n_kv_heads,
                "d": (c.head_dim + c.v_head_dim) / 2, "layers": 1,
                "rows": (kinds.count("full") * [list(r) for r in rows]
                         + kinds.count("window")
                         * _cohere.masked_rows(rows, c.sliding_window)),
                "classes": classes}


def build(config: dict, devices, ref, key) -> ServeSystem:
    return ServeSystem(config, devices, ref, key)
