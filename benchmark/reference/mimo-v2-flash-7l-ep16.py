"""The plain reference of configuration ``mimo-v2-flash-7l-ep16``: the
MiMo-V2-Flash forward in float32 ``jax.numpy`` (``_mimo_v2.py``, beside this
file: window-128 layers with a learned sink a head beside full layers of
another key/value geometry, keys 192 wide and values 128, sigmoid-routed
experts), given the same share as the program (experts 64-79 of 256, the same
slice of the vocabulary), and the one place that says how the published sizes
become the program's settings."""

from _mimo_v2 import logits, program_config, routing, tree  # noqa: F401
