"""The plain reference of configuration ``trinity-large-preview-5l-ep8``: the afmoe
(Trinity) forward in float32 ``jax.numpy`` (``_afmoe.py``, beside this file),
given the same share as the program (the same 32 of 256 experts, the same
slice of the vocabulary), and the one place that says how the published
sizes become the program's settings."""

from _afmoe import logits, program_config, routing, tree  # noqa: F401
