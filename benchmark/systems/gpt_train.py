"""System under test for training cells: the jitted step that
``paddle_tpu.parallel.make_sharded_train_step`` returns, built from a
configuration file and a traffic file.  The weights and the optimizer's
state are the benchmark's, born on the device from the seed."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


class TrainSystem:
    def __init__(self, config: dict, traffic: dict, devices, ref):
        from paddle_tpu.distributed.process_mesh import build_mesh
        from paddle_tpu.models.gpt import GPTConfig
        from paddle_tpu.parallel import make_sharded_train_step

        m, t = config["model"], config["train"]
        self.model, self.train, self.ref = m, t, ref
        self.devices = list(devices)
        self.batch, self.seq = traffic["batch"], traffic["seq_len"]
        self.cfg = GPTConfig(
            vocab_size=m["vocab_size"], hidden=m["d_model"],
            n_layers=m["n_layers"], n_heads=m["n_heads"], seq_len=m["n_ctx"],
            ffn_mult=m["ffn_mult"], eps=m["layer_norm_eps"],
            unroll=t["unroll"], remat=t["remat"])
        self.mesh = build_mesh(tuple(traffic["mesh"]), ("dp", "pp", "mp"),
                               devices=self.devices)
        self.step, self.p_abs, self.o_abs = make_sharded_train_step(
            self.cfg, self.mesh, lr=t["lr"], zero1=traffic["zero1"],
            m_dtype=t["m_dtype"], v_dtype=t["v_dtype"], weights=t["weights"],
            abstract=True)
        one = SingleDeviceSharding(self.devices[0])
        self._gen = jax.jit(
            lambda k: jax.tree.map(lambda a, s: a.astype(s.dtype),
                                   ref.make_params(m, k), self.p_abs),
            out_shardings=one)
        self._zeros = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 self.o_abs),
            out_shardings=jax.tree.map(lambda s: s.sharding, self.o_abs))
        self._batches = jax.jit(
            lambda k, n: ref.make_batches(m, k, n, self.batch, self.seq),
            static_argnums=1, out_shardings=one)

    def initial_params(self, key):
        """The seed's weights in the program's types and layout."""
        return jax.device_put(
            self._gen(key), jax.tree.map(lambda s: s.sharding, self.p_abs))

    def new_state(self, key):
        return self.initial_params(key), self._zeros()

    def batches(self, key, n: int):
        """(device-resident (tokens, labels) pairs in the step's layout,
        the raw [n, batch, seq + 1] ids on the host for the reference)."""
        raw = jax.device_get(self._batches(key, n))
        feed = [(self.step.put_batch(b[:, :-1]), self.step.put_batch(b[:, 1:]))
                for b in raw]
        return feed, raw

    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    def flops_per_token(self) -> float:
        """6 P_dense + 6 L S H: what forward and backward need; the remat
        pass's recomputation is not counted.  (bench.py's formula.)"""
        H, L, V = self.cfg.hidden, self.cfg.n_layers, self.cfg.vocab_size
        F = self.cfg.ffn_mult * H
        p_dense = V * H + L * (4 * H * H + 2 * H * F)
        return 6.0 * p_dense + 6.0 * L * self.seq * H

    def local_attention_shape(self) -> dict:
        dp, _, mp = self.mesh.devices.shape
        return {"b": self.batch // dp, "heads": self.cfg.n_heads // mp,
                "s": self.seq, "d": self.cfg.head_dim,
                "layers": self.cfg.n_layers}

    def ce_shape(self) -> dict:
        return {"tokens": self.batch * self.seq, "hidden": self.cfg.hidden,
                "vocab": self.cfg.vocab_size}

    def memory_analysis(self, params, opt, toks, labs) -> dict:
        """Arguments + temporaries of the compiled step on one device."""
        with jax.sharding.set_mesh(self.mesh):
            ma = self.step.jitted.lower(params, opt, toks,
                                        labs).compile().memory_analysis()
        return {"argument_bytes": ma.argument_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes}


def build(config: dict, traffic: dict, devices, ref) -> TrainSystem:
    return TrainSystem(config, traffic, devices, ref)

