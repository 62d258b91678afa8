"""The Mistral family's plain reference, shared with mistral-7b-d16."""

from benchmark.harness import load_module

globals().update({k: v for k, v in vars(
    load_module("configs/mistral-7b-d16.reference.py")).items()
    if not k.startswith("__")})
