"""System under test for serving cells of the latent-attention,
routed-expert family: ``ServingEngine`` over ``models/mla_moe.py`` at the
configuration's widths, holding the experts and the vocabulary slice the
configuration's deployment gives this chip, with the engine geometry the
configuration file states.  The interface is ``llama_serve.py``'s (the
engine's jitted step has the same operands, so its recording of the row
tables, ``memory_analysis`` and ``free`` are taken from there)."""

from __future__ import annotations

import jax

from benchmark import harness

_llama = harness.load_module("systems/llama_serve.py")


class ServeSystem(_llama.ServeSystem):
    def __init__(self, config: dict, devices, ref, key):
        from paddle_tpu.inference.serving import Request, ServingEngine
        from paddle_tpu.models.mla_moe import MlaMoeConfig

        m, e = config["model"], config["engine"]
        self.model, self.geometry, self.Request = m, e, Request
        self.cfg = MlaMoeConfig.from_hf(
            m, n_routed_experts=m["n_routed_experts_published"],
            held=tuple(m["held_experts"]), max_seq_len=e["max_seq"])
        with jax.default_device(devices[0]):
            params = jax.jit(lambda k: ref.make_params(m, k))(key)
            self.engine = ServingEngine(
                self.cfg, params=params, max_batch=e["max_batch"],
                page_size=e["page_size"], max_seq=e["max_seq"],
                n_pages=e["n_pages"], prefill_budget=e["prefill_budget"],
                prefix_cache=e["prefix_cache"], qb=e["qb"])

    def counters(self) -> dict:
        out = super().counters()
        out.update({k: self.engine.stats[k]
                    for k in self.engine.model.stats_keys})
        return out

    def matmul_flops_per_token(self) -> float:
        """2 x the matrix weights a token passes through in the layers:
        the latent attention's projections, the dense layer, and in each
        expert layer the router, the shared expert and the routed experts
        the token really met HERE (from the engine's assignment counters:
        about half an expert of its eight, the rest are held elsewhere)."""
        c, st = self.cfg, self.engine.stats
        H, nH = c.hidden, c.n_heads
        attn = (H * c.q_lora_rank
                + c.q_lora_rank * nH * (c.qk_nope_dim + c.qk_rope_dim)
                + H * (c.kv_lora_rank + c.qk_rope_dim)
                + c.kv_lora_rank * nH * (c.qk_nope_dim + c.v_head_dim)
                + nH * c.v_head_dim * H)
        expert = 3 * H * c.moe_hidden
        met = (c.experts_per_token * st["moe_assigned_held"]
               / max(1, st["moe_assigned_all"]))
        n_moe = c.n_layers - c.n_dense_layers
        return 2.0 * (c.n_layers * attn
                      + c.n_dense_layers * 3 * H * c.ffn_hidden
                      + n_moe * (H * c.n_routed_experts
                                 + (c.n_shared_experts + met) * expert))

    def attention_shape(self, rows: list) -> dict:
        """``work/ragged_paged_attention.py`` counts 4 heads d flops a
        query-key pair; the model's own attention (expanded form) is 2
        heads (qk_dim + v_dim), so d is their mean.  The latent widths
        ride along for ``work/mla_paged_attention.py``."""
        c = self.cfg
        qk = c.qk_nope_dim + c.qk_rope_dim
        return {"heads": c.n_heads, "kv_heads": 1,
                "d": (qk + c.v_head_dim) / 2.0, "layers": c.n_layers,
                "rows": rows, "qk_dim": qk, "v_dim": c.v_head_dim,
                "latent": c.kv_lora_rank + c.qk_rope_dim}


def build(config: dict, devices, ref, key) -> ServeSystem:
    return ServeSystem(config, devices, ref, key)
