"""The plain reference of configuration ``granite-4.0-h-micro``: Granite
4.0-H's forward in float32 ``jax.numpy``, the scan layers as a left-to-right
recurrence (``_granite_hybrid.py``, beside this file), and the one place that
says how the published sizes become the program's settings."""

from _granite_hybrid import logits, program_config, tree  # noqa: F401
