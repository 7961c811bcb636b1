"""The plain reference of configuration ``minicpm-sala-12l``: MiniCPM-SALA's
forward in float32 ``jax.numpy``, the lightning layers as a left-to-right
recurrence and the sparse layers' choice of blocks by a full sort
(``_minicpm_sala.py``, beside this file), and the one place that says how the
published sizes become the program's settings."""

from _minicpm_sala import logits, program_config, tree  # noqa: F401
