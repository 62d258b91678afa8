"""System under test for serving cells: ``ServingEngine`` over
``models/llama.py`` at the configuration's widths, with the engine
geometry the configuration file states.  The weights are the benchmark's,
born on the device in one jitted call from the seed, in bf16."""

from __future__ import annotations

import jax
import jax.numpy as jnp


class ServeSystem:
    def __init__(self, config: dict, devices, ref, key):
        from paddle_tpu.inference.serving import Request, ServingEngine
        from paddle_tpu.models.llama import LlamaConfig

        m, e = config["model"], config["engine"]
        self.model, self.geometry, self.Request = m, e, Request
        self.cfg = LlamaConfig(
            vocab_size=m["vocab_size"], hidden=m["hidden_size"],
            n_layers=m["num_hidden_layers"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            ffn_hidden=m["intermediate_size"], max_seq_len=e["max_seq"],
            rope_theta=m["rope_theta"], rms_eps=m["rms_norm_eps"],
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        with jax.default_device(devices[0]):
            params = jax.jit(lambda k: ref.make_params(m, k))(key)
            self.engine = ServingEngine(
                self.cfg, params=params, max_batch=e["max_batch"],
                page_size=e["page_size"], max_seq=e["max_seq"],
                n_pages=e["n_pages"], prefill_budget=e["prefill_budget"],
                prefix_cache=e["prefix_cache"], qb=e["qb"])

    def request(self, rid: int, prompt, max_new_tokens: int):
        return self.Request(rid=rid, prompt=prompt,
                            max_new_tokens=max_new_tokens)   # greedy

    def counters(self) -> dict:
        st = self.engine.stats
        out = {k: st[k] for k in (
            "unified_steps", "decode_active_tokens", "decode_slot_tokens",
            "preemptions", "prefill_tokens", "prefill_cached_tokens")}
        out["prompt_tokens_admitted"] = (st["prefill_tokens"]
                                         + st["prefill_cached_tokens"])
        return out

    def matmul_flops_per_token(self) -> float:
        """2 x the matrix weights a token passes through in the layers."""
        m = self.model
        H, F = m["hidden_size"], m["intermediate_size"]
        d = H // m["num_attention_heads"]
        q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
        return 2.0 * m["num_hidden_layers"] * (
            H * (q + 2 * kv) + q * H + 3 * H * F)

    def head_flops_per_output_token(self) -> float:
        return 2.0 * self.model["hidden_size"] * self.model["vocab_size"]

    def attention_shape(self, rows: list) -> dict:
        m = self.model
        return {"heads": m["num_attention_heads"],
                "kv_heads": m["num_key_value_heads"],
                "d": m["hidden_size"] // m["num_attention_heads"],
                "layers": m["num_hidden_layers"], "rows": rows}

    def kv_pool_shapes(self) -> list:
        """Result shapes that mark an op as touching the page pool: the
        whole pool, or one layer's slice of it (k and v layouts)."""
        k, v = self.engine.k_pages.shape, self.engine.v_pages.shape
        return [list(s) for a in (k, v)
                for s in (a, (1,) + a[1:], a[1:])]

    def record_dispatches(self) -> list:
        """Wrap the engine's jitted step so that each dispatch's row table
        (row_slot, pos0, n_valid: device arrays the engine built) is kept
        by reference; used in the traced segment only."""
        inner, kept = self.engine._unified, []

        def recording(params, k_pages, v_pages, tokens, prev_out, chain_mask,
                      chain_row, ptable, row_slot, pos0, n_valid, *rest):
            kept.append((row_slot, pos0, n_valid))
            return inner(params, k_pages, v_pages, tokens, prev_out,
                         chain_mask, chain_row, ptable, row_slot, pos0,
                         n_valid, *rest)

        self.engine._unified = recording
        self._inner = inner
        return kept

    def stop_recording(self) -> None:
        self.engine._unified = self._inner

    def rows_of(self, kept: list) -> list:
        """[pos0, n] per (tick, request) from the recorded row tables."""
        import numpy as np

        B, rows = self.geometry["max_batch"], []
        for row_slot, pos0, n_valid in kept:
            rs, p0, nv = (np.asarray(a) for a in (row_slot, pos0, n_valid))
            for s in np.unique(rs[rs < B]):
                sel = rs == s
                rows.append([int(p0[sel].min()), int(nv[sel].sum())])
        return rows

    def memory_analysis(self) -> dict:
        ma = self.engine.lower_unified().compile().memory_analysis()
        return {"argument_bytes": ma.argument_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes}

    def free(self) -> None:
        eng = self.engine
        eng.params = eng.k_pages = eng.v_pages = None
        eng._inflight = eng._prev_out_dev = None
        self.engine = None


def build(config: dict, devices, ref, key) -> ServeSystem:
    return ServeSystem(config, devices, ref, key)
