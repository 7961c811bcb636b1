"""The plain reference of configuration ``dots3-note-prev-5l-ep8``: the
dots3-note forward in float32 ``jax.numpy`` (``_dots3_note.py``, beside this
file: latent attention with a query latent, a learned top-k selection on the
full layers, a second latent geometry under a window on the sliding ones,
headwise gates), given the same share as the program (the same 32 of 256
experts, the same slice of the vocabulary), and the one place that says how
the published sizes become the program's settings."""

from _dots3_note import (logits, program_config, routing,  # noqa: F401
                         selections, tree)
