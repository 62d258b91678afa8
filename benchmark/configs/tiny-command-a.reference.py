"""The command-a-plus family's plain reference, shared with command-a-plus-ep8."""

from benchmark.harness import load_module

globals().update({k: v for k, v in vars(
    load_module("configs/command-a-plus-ep8.reference.py")).items()
    if not k.startswith("__")})
