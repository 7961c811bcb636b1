"""The plain reference of configuration ``lfm2-24b-a2b-10l``: LFM2-MoE's
forward in float32 ``jax.numpy``, the short convolutions as a left-to-right
sum over shifted rows and every expert held as the program holds them
(``_lfm2_moe.py``, beside this file), and the one place that says how the
published sizes become the program's settings."""

from _lfm2_moe import logits, program_config, routing, tree  # noqa: F401
