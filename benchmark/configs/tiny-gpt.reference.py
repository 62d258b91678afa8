"""The gpt3 family's plain reference, shared with gpt3-1.3b."""

from benchmark.harness import load_module

globals().update({k: v for k, v in vars(
    load_module("configs/gpt3-1.3b.reference.py")).items()
    if not k.startswith("__")})
