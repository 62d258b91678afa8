"""The JoyAI-LLM-Flash family's plain reference, shared with joyai-flash-ep16."""

from benchmark.harness import load_module

globals().update({k: v for k, v in vars(
    load_module("configs/joyai-flash-ep16.reference.py")).items()
    if not k.startswith("__")})
