"""Qwen2 (hf ``Qwen2ForCausalLM``) is the dense GQA decoder of
``qwen3.py`` with a bias on q, k and v and no q/k RMSNorm, both read
from the configuration."""

from bench.models.qwen3 import (  # noqa: F401
    LEAVES, dims, draw, program_config, shapes)
