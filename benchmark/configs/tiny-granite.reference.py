"""The granite hybrid family's plain reference, shared with granite-4.0-h-micro."""

from benchmark.harness import load_module

globals().update({k: v for k, v in vars(
    load_module("configs/granite-4.0-h-micro.reference.py")).items()
    if not k.startswith("__")})
