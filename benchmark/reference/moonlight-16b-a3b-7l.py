"""The plain reference of configuration ``moonlight-16b-a3b-7l``: the
DeepSeek-V3-style forward with latent attention in float32 ``jax.numpy``
(``_deepseek_mla.py``, beside this file), every expert held as the program
holds them, and the one place that says how the published sizes become the
program's settings."""

from _deepseek_mla import logits, program_config, routing, tree  # noqa: F401
