"""Plain reference of the Qwen2 dense decoder (hf Qwen/Qwen2-72B,
modeling_qwen2): the math of ``qwen3.py``, which adds the q, k and v
bias where the weights carry one and applies the q/k RMSNorm only where
the weights carry it, so a Qwen2 layer (bias, no q/k norm) is its
special case."""

from bench.reference.qwen3 import HI, head, hidden, layer, loss, mm  # noqa: F401
