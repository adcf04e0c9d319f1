"""Reference of qwen2_7b: the decoder reference (GQA with QKV bias, RoPE,
SwiGLU FFN with block-sparse junctions, untied head), nothing added."""
from bench.reference.decoder import served_gaps  # noqa: F401
