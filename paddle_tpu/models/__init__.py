"""Flagship model zoo (reference: python/paddle/vision/models + the GPT/
BERT/LLaMA configs exercised by the fleet test-suite and BASELINE.md).

Trained: ``gpt.py`` (and ``bert.py``).  Served through
``inference/serving.py`` on the seam ``seam.py``: ``llama.py`` (GQA
pages), ``mla_moe.py`` (latent pages, routed experts), ``cohere_moe.py``
(window and global cache classes, a parallel block over routed experts),
``granite_hybrid.py`` (state-space layers in a state class of slots and
snapshots beside a few attention layers' pages); ``routed_experts.py`` is
the two routed families' expert layer."""

from .gpt import GPT, GPTConfig, gpt_presets, init_params, model_apply, loss_fn

__all__ = ["GPT", "GPTConfig", "gpt_presets", "init_params", "model_apply",
           "loss_fn"]
