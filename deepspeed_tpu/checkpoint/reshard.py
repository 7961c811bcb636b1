"""Resharding restore — checkpoint layout conversion as a sharding-spec transform.

Per "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv:2004.13336), retargeting a checkpoint at a new topology is
a transform on the sharding/layout SPEC, not a checkpoint-format special
case.  Named shardings already make the *mesh* half of that free (orbax
restores any leaf into any sharding of the same global shape); this module
supplies the other half — the *structural* conversion between physical
parameter layouts that shape the pytree itself:

- the plain engine's per-layer tree (``backbone.block_{i}.*``),
- the pipeline engine's stage-stacked tree (``blocks.*`` leaves of shape
  ``[S, L/S, ...]`` with the stage dim sharded over ``pp``).

Every checkpoint is reduced to one LOGICAL namespace — the per-layer
(unstacked) dotted paths of the plain model — plus a ``layout`` descriptor
saying how the source engine physically laid those tensors out.  Restore
re-lays the logical fragments out for the TARGET engine and lets the
target's own shardings place them on its mesh, so any (dp, fsdp, pp, tp,
ZeRO-stage) source restores into any other (reference: the whole
checkpoint/ds_to_universal.py extract/merge pipeline exists to do this for
torch checkpoints).

Layout descriptors (stored in universal meta.json ``layout`` and in the
orbax checkpoint's ``client_state``):

- ``{"kind": "flat"}``                      — tree paths ARE logical paths
- ``{"kind": "pipe", "num_stages": S, "num_layers": L}``
                                            — pipeline-stacked (PipeGPT)
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import numpy as np

Fragments = Dict[str, Dict[str, np.ndarray]]

# physical pipe path → logical path for the non-stacked parameters
# (the same correspondence pipe/module.py gpt_params_to_pipe encodes for
# live params)
_PIPE_TO_LOGICAL = {
    "params.embed": "params.backbone.wte",
    "params.wpe": "params.backbone.wpe",
    "params.final_norm_scale": "params.backbone.final_norm.scale",
    "params.final_norm_bias": "params.backbone.final_norm.bias",
    "params.head": "params.lm_head",
}
_LOGICAL_TO_PIPE = {v: k for k, v in _PIPE_TO_LOGICAL.items()}
_PIPE_BLOCK_PREFIX = "params.blocks."
_LOGICAL_BLOCK_RE = re.compile(r"^params\.backbone\.block_(\d+)\.(.+)$")


def flat_layout() -> dict:
    return {"kind": "flat"}


def engine_layout(engine) -> dict:
    """The physical-layout descriptor of an engine's parameter tree."""
    model = engine.model
    if getattr(model, "is_pipeline", False) and hasattr(model, "num_stages"):
        return {"kind": "pipe", "num_stages": int(model.num_stages),
                "num_layers": int(model.cfg.num_layers)}
    return flat_layout()


def _pipe_dims(layout: dict) -> Tuple[int, int, int]:
    S = int(layout["num_stages"])
    L = int(layout["num_layers"])
    if S <= 0 or L % S:
        raise ValueError(f"bad pipe layout {layout}: num_layers must divide "
                         f"into num_stages")
    return S, L, L // S


def to_logical(frags: Fragments, layout: Optional[dict]) -> Fragments:
    """Source-physical fragments → logical per-layer fragments."""
    if not layout or layout.get("kind", "flat") == "flat":
        return frags
    if layout["kind"] != "pipe":
        raise ValueError(f"unknown checkpoint layout kind "
                         f"{layout['kind']!r}")
    S, L, Lps = _pipe_dims(layout)
    out: Fragments = {}
    for path, entry in frags.items():
        if path.startswith(_PIPE_BLOCK_PREFIX):
            sub = path[len(_PIPE_BLOCK_PREFIX):]
            for i in range(L):
                s, li = divmod(i, Lps)
                out[f"params.backbone.block_{i}.{sub}"] = {
                    k: np.asarray(v)[s, li] for k, v in entry.items()}
        else:
            out[_PIPE_TO_LOGICAL.get(path, path)] = entry
    return out


def from_logical(frags: Fragments, layout: Optional[dict]) -> Fragments:
    """Logical fragments → the TARGET engine's physical layout."""
    if not layout or layout.get("kind", "flat") == "flat":
        return frags
    if layout["kind"] != "pipe":
        raise ValueError(f"unknown checkpoint layout kind "
                         f"{layout['kind']!r}")
    S, L, Lps = _pipe_dims(layout)
    out: Fragments = {}
    blocks: Dict[str, Dict[int, Dict[str, np.ndarray]]] = {}
    for path, entry in frags.items():
        m = _LOGICAL_BLOCK_RE.match(path)
        if m:
            i, sub = int(m.group(1)), m.group(2)
            blocks.setdefault(sub, {})[i] = entry
        else:
            out[_LOGICAL_TO_PIPE.get(path, path)] = entry
    for sub, per_layer in blocks.items():
        missing = [i for i in range(L) if i not in per_layer]
        if missing:
            raise ValueError(
                f"checkpoint covers layers {sorted(per_layer)} of "
                f"'{sub}' but the pipeline layout needs all {L} "
                f"(missing {missing[:4]}{'...' if len(missing) > 4 else ''})")
        keys = per_layer[0].keys()
        entry = {}
        for k in keys:
            arrs = [np.asarray(per_layer[i][k]) for i in range(L)]
            entry[k] = np.stack(arrs).reshape((S, Lps) + arrs[0].shape)
        out[_PIPE_BLOCK_PREFIX + sub] = entry
    return out


def convert_layout(frags: Fragments, src_layout: Optional[dict],
                   dst_layout: Optional[dict]) -> Fragments:
    """source physical → logical → target physical (identity when both are
    flat; a pipe→pipe restore across different stage counts unstacks and
    restacks through the logical view)."""
    return from_logical(to_logical(frags, src_layout), dst_layout)


# ---------------------------------------------------------------------------
# cross-topology orbax restore (engine.load_checkpoint fallback)
# ---------------------------------------------------------------------------

class _Carrier:
    """Duck-typed TrainState for universal.state_fragments over a raw
    (target-less) orbax restore."""

    def __init__(self, raw: Dict[str, Any]):
        self.params = raw["params"]
        self.opt_state = raw.get("opt_state", ())
        self.step = raw.get("step", 0)


def fragments_from_orbax(load_dir: str, tag: str) -> Fragments:
    """Restore an orbax tag WITHOUT a target structure (host numpy) and
    reduce it to universal fragments — fp32 masters + Adam moments when the
    saved optimizer carried them, raw params otherwise."""
    import os

    from deepspeed_tpu import checkpoint as ckpt
    from deepspeed_tpu.checkpoint import universal
    path = os.path.join(os.path.abspath(load_dir), tag, "state")
    # the package's long-lived checkpointer — a fresh instance per restore
    # would serialize on its own setup (see checkpoint/__init__.py)
    raw = ckpt._checkpointer().restore(path)
    return universal.state_fragments(_Carrier(raw))
