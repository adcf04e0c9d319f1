"""Reference of granite_moe_1b_a400m: the decoder reference (GQA without
bias, RoPE, 32 routed experts top-8 with block-sparse expert junctions,
tied head) and its training step."""
from bench.reference.decoder import served_gaps  # noqa: F401
from bench.reference.train import three_steps  # noqa: F401
