"""System under test for serving cells of the state-space-and-attention
hybrid family: ``ServingEngine`` over ``models/granite_hybrid.py`` at the
configuration's widths, whole, with the engine geometry the configuration
file states (``engine.n_pages`` is the attention layers' page pool,
``engine.state_slots`` the live slots of the state class, one an engine
row, ``engine.state_snapshots`` its snapshot slots).  The interface is
``llama_serve.py``'s: the engine's jitted step keeps its first eleven
operands, so its recording of the row tables and ``memory_analysis`` are
taken from there."""

from __future__ import annotations

import numpy as np

import jax

from benchmark import harness

_llama = harness.load_module("systems/llama_serve.py")


class ServeSystem(_llama.ServeSystem):
    KEEP_STATES = 4

    def __init__(self, config: dict, devices, ref, key):
        from paddle_tpu.inference.serving import Request, ServingEngine
        try:
            from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
        except ImportError as e:
            # a checkout from before the model (the parent of the PR that
            # added it): no result, at once
            raise harness.BenchError(
                f"this program cannot serve the configuration: {e}") from e

        m, e = config["model"], config["engine"]
        if e["state_slots"] != e["max_batch"]:
            raise harness.BenchError(
                "engine.state_slots is one live slot an engine row: "
                f"{e['state_slots']} != max_batch {e['max_batch']}")
        self.model, self.geometry, self.Request = m, e, Request
        self.cfg = GraniteHybridConfig.from_hf(m, max_seq_len=e["max_seq"])
        with jax.default_device(devices[0]):
            params = jax.jit(lambda k: ref.make_params(m, k))(key)
            self.engine = ServingEngine(
                self.cfg, params=params, max_batch=e["max_batch"],
                page_size=e["page_size"], max_seq=e["max_seq"],
                n_pages=e["n_pages"],
                class_pages={"state": e["state_snapshots"]},
                prefill_budget=e["prefill_budget"],
                prefix_cache=e["prefix_cache"], qb=e["qb"])

    def counters(self) -> dict:
        """The engine's counters, its state class's, and
        ``kv_live_centibytes``: the bytes of the pages and of the state
        slots live requests held, summed over ticks, in hundredths, so
        that ``counter_ratio`` (a percentage) over ``context_tokens_live``
        reads bytes a context token, pages and state together."""
        eng, out = self.engine, super().counters()
        st = eng.stats
        out.update({k: st[k] for k in (
            "context_tokens_live", "state_slots_live", "state_bytes_live",
            "state_snapshots_taken", "state_snapshots_hit",
            "state_snapshots_evicted", "state_snapshots_unavailable",
            "prefix_state_lost_tokens", "admitted_with_cached_prefix",
            "preempt_resumed_from_snapshot")})
        pages = st[f"pages_live.{eng.classes[0].name}"]
        out["pages_live.global"] = pages
        out["kv_live_centibytes"] = (
            pages * eng.kv_bytes_per_page(0) + st["state_bytes_live"]) / 100.0
        return out

    def _ssm(self) -> dict:
        c = self.cfg
        return {"heads": c.mamba_heads, "head_dim": c.mamba_head_dim,
                "d_state": c.d_state,
                "layers": c.layer_types.count("mamba"),
                "qb": self.geometry["qb"], "itemsize": 2,
                "state_itemsize": 4,
                "chained": "an entry of rows is one request's tokens of one "
                           "tick: consecutive rows, the state in once and "
                           "out once"}

    def matmul_flops_per_token(self) -> float:
        """2 x the matrix weights a token passes through in the layers
        (the mixers' and the attention's projections, the feed-forward),
        and the recurrence's own operations a token
        (work/ragged_ssm_scan.py), so that the step's share of the peak
        counts the model's work."""
        c = self.cfg
        H, F, kv = c.hidden, c.ffn_hidden, c.n_kv_heads * c.head_dim
        n_m = c.layer_types.count("mamba")
        n_a = c.layer_types.count("attention")
        mixer = H * (c.d_inner + c.conv_dim + c.mamba_heads) + c.d_inner * H
        scan = harness.load_module("work/ragged_ssm_scan.py").flops_per_token(
            self._ssm())
        return (2.0 * (n_m * mixer + n_a * (2 * H * H + 2 * H * kv)
                       + c.n_layers * 3 * H * F)
                + n_m * (scan + 2.0 * c.d_conv * c.conv_dim))

    def attention_shape(self, rows: list) -> dict:
        """The four attention layers' shape for
        ``work/ragged_paged_attention.py`` (the model's heads of 64, not
        the pairs the pages hold), and under ``ssm`` the recurrence's for
        ``work/ragged_ssm_scan.py`` on the same rows."""
        c = self.cfg
        return {"heads": c.n_heads, "kv_heads": c.n_kv_heads,
                "d": c.head_dim, "layers": c.layer_types.count("attention"),
                "rows": rows, "ssm": self._ssm()}

    def kv_pool_shapes(self) -> list:
        """The page pools and the state planes' pools, whole and one
        layer's slice, and one slot of the recurrence's state.  One slot
        of the conv plane is a flat ``[13056]``: as a name's suffix that
        is every op whose result ends in 13056 channels (the rows' gather
        of their conv states among them), so it is left out; a copy of a
        slot of it would be 26 KB."""
        eng = self.engine
        st = eng._state
        out = [list(s) for a in (eng.k_pages, eng.v_pages, st.k_pages,
                                 st.v_pages)
               for s in (a.shape, (1,) + a.shape[1:], a.shape[1:])]
        slot = st.v_pages.shape[2:]
        return out + [list(slot), [1, *slot]]

    def free(self) -> None:
        """Before the pools go: keep, on the host, the recurrence's state
        under ``KEEP_STATES`` snapshots of live requests, half of them
        the nearest to a context's start (where a state that was not the
        request's own to start from still shows) and half the furthest
        into one (where a recurrence's rounding has gathered), for the
        kind's comparison of the state itself (``kept_states``:
        ``(request, tokens, state [layers, heads, head width, d_state]
        float32)``, the state after exactly the request's first
        ``tokens`` tokens, as the pool held it)."""
        eng, c = self.engine, self.cfg
        snaps = sorted(eng.cached_snapshots(), key=lambda h: h[1])
        near = self.KEEP_STATES // 2
        held = snaps[:near] + snaps[near:][near - self.KEEP_STATES:]
        self.kept_states = []
        if held:
            tiles = np.asarray(eng._state.v_pages[
                :, np.asarray([slot for _r, _t, slot in held])], np.float32)
            # a slot is [heads / P, d_state, P x head width]: P heads side
            # by side along the lanes, the state transposed
            L, n, nT, N, _lanes = tiles.shape
            states = tiles.reshape(L, n, nT, N, -1, c.mamba_head_dim
                                   ).transpose(1, 0, 2, 4, 5, 3).reshape(
                n, L, c.mamba_heads, c.mamba_head_dim, N)
            self.kept_states = [(req, tokens, states[i])
                                for i, (req, tokens, _s) in enumerate(held)]
        eng._state.k_pages = eng._state.v_pages = None
        super().free()


def build(config: dict, devices, ref, key) -> ServeSystem:
    return ServeSystem(config, devices, ref, key)
