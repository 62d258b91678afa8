"""The MiMo-V2 family's plain reference, shared with mimo-v2.5-ep16."""

from benchmark.harness import load_module

globals().update({k: v for k, v in vars(
    load_module("configs/mimo-v2.5-ep16.reference.py")).items()
    if not k.startswith("__")})
