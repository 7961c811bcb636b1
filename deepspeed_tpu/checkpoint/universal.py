"""Universal checkpointing — per-parameter fp32 fragment export/import.

Reference: checkpoint/ds_to_universal.py (shard extract/merge pipeline into
``zero/<param_name>/fp32.pt`` fragment dirs), checkpoint/universal_checkpoint.py
(load_hp_checkpoint_state), utils/zero_to_fp32.py (offline consolidation).

The TPU engine's orbax checkpoints already reshard freely on load (named
shardings), so the reference's *topology* motivation disappears — what this
module adds is the other half of "universal": a framework-neutral on-disk
layout that

- any tool can read without orbax/jax (one little-endian ``.npy`` per tensor),
- carries TRUE fp32 master weights + optimizer moments (not the bf16 params),
- and can ingest reference-style torch fragments (``fp32.pt``) for
  cross-framework migration.

Layout (mirrors ds_to_universal's output shape)::

    out_dir/
      meta.json                      # step, format tag, param manifest
      zero/
        <dotted.param.path>/         # e.g. backbone.block_0.Attention_0.wq
          fp32.npy                   # master weights (fp32)
          exp_avg.npy                # Adam first moment, when present
          exp_avg_sq.npy             # Adam second moment, when present
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

FORMAT = "deepspeed_tpu_universal/1"
_FRAGMENT_KEYS = ("fp32", "exp_avg", "exp_avg_sq")


# ---------------------------------------------------------------------------
# crash-safe commit protocol (same ordering as the orbax tag commit in
# checkpoint/__init__.py): .in_progress marker → every fragment byte + meta
# durable → marker off → 'latest_universal' pointer moves.  A death at any
# point leaves either a torn export that load_universal REFUSES (marker
# present / meta missing) and latest_universal() skips, or a committed
# export the pointer may trail — the previous complete export resumes
# either way.
# ---------------------------------------------------------------------------

def _begin_export(out_dir: str) -> str:
    from deepspeed_tpu.checkpoint import IN_PROGRESS_FILE
    from deepspeed_tpu.runtime import faults
    os.makedirs(out_dir, exist_ok=True)
    marker = os.path.join(out_dir, IN_PROGRESS_FILE)
    with open(marker, "w") as f:
        f.write(str(time.time()))
    faults.fire("universal.pre_fragments", out_dir=out_dir)
    return marker


def _commit_export(out_dir: str, marker: str,
                   run_dir: Optional[str] = None) -> str:
    from deepspeed_tpu.checkpoint import UNIVERSAL_LATEST_FILE
    from deepspeed_tpu.runtime import faults
    faults.fire("universal.pre_commit", out_dir=out_dir)
    os.remove(marker)                    # data durable → marker off
    if run_dir:
        faults.fire("universal.pre_pointer", out_dir=out_dir)
        ptr = os.path.join(run_dir, UNIVERSAL_LATEST_FILE)
        rel = os.path.relpath(os.path.abspath(out_dir),
                              os.path.abspath(run_dir))
        target = out_dir if rel.startswith(os.pardir) else rel
        with open(ptr + ".tmp", "w") as f:
            f.write(target)
        os.replace(ptr + ".tmp", ptr)    # pointer moves last, atomically
    return out_dir


def _write_meta_json(out_dir: str, step: int, manifest: dict,
                     layout: Optional[dict]) -> None:
    from deepspeed_tpu.runtime import faults
    faults.fire("universal.pre_meta", out_dir=out_dir)
    meta = {"format": FORMAT, "step": int(step), "params": manifest}
    if layout:
        # logical layout metadata: how the SOURCE engine laid these params
        # out (pipeline stages, zero stage, mesh) — restore-time layout conversion
        # (checkpoint/reshard.py) keys on it.  Fragments on disk are always
        # in the LOGICAL (per-layer, unstacked) namespace.
        meta["layout"] = layout
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)


# ---------------------------------------------------------------------------
# generic pytree surgery: find / rewrite optimizer sub-states by type
# ---------------------------------------------------------------------------

def _is_namedtuple(x) -> bool:
    return isinstance(x, tuple) and hasattr(x, "_fields")


def _find_nodes(node, pred, out):
    """Collect all sub-nodes matching ``pred`` (no descent into matches)."""
    if pred(node):
        out.append(node)
        return out
    if _is_namedtuple(node):
        for f in node._fields:
            _find_nodes(getattr(node, f), pred, out)
    elif isinstance(node, (tuple, list)):
        for x in node:
            _find_nodes(x, pred, out)
    elif isinstance(node, dict):
        for x in node.values():
            _find_nodes(x, pred, out)
    return out


def _rewrite_nodes(node, visit):
    """Rebuild the tree, replacing any node where ``visit`` returns non-None."""
    new = visit(node)
    if new is not None:
        return new
    if _is_namedtuple(node):
        return type(node)(*[_rewrite_nodes(getattr(node, f), visit)
                            for f in node._fields])
    if isinstance(node, tuple):
        return tuple(_rewrite_nodes(x, visit) for x in node)
    if isinstance(node, list):
        return [_rewrite_nodes(x, visit) for x in node]
    if isinstance(node, dict):
        return {k: _rewrite_nodes(v, visit) for k, v in node.items()}
    return node


def _adam_states(opt_state):
    """ScaleByAdamState nodes — typed (live engine state) or the dict form an
    orbax restore-without-target produces."""
    import optax

    def pred(n):
        return (isinstance(n, optax.ScaleByAdamState)
                or (isinstance(n, dict) and set(n) == {"count", "mu", "nu"}))

    return [{"mu": n["mu"], "nu": n["nu"]} if isinstance(n, dict)
            else {"mu": n.mu, "nu": n.nu}
            for n in _find_nodes(opt_state, pred, [])]


def _master_states(opt_state):
    from deepspeed_tpu.runtime.zero import MasterWeightsState

    def pred(n):
        return (isinstance(n, MasterWeightsState)
                or (isinstance(n, dict) and set(n) == {"master", "inner"}))

    return [{"master": n["master"]} if isinstance(n, dict)
            else {"master": n.master}
            for n in _find_nodes(opt_state, pred, [])]


# ---------------------------------------------------------------------------
# path helpers
# ---------------------------------------------------------------------------

def _flatten_params(params) -> Dict[str, Any]:
    """Nested dict tree → {"a.b.c": leaf} with deterministic dotted paths."""
    flat = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], prefix + (str(k),))
        elif isinstance(node, (list, tuple)):   # infinity layout: layers list
            for i, v in enumerate(node):
                walk(v, prefix + (str(i),))
        else:
            flat[".".join(prefix)] = node

    walk(params, ())
    return flat


def _unflatten_params(flat: Dict[str, Any]) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    for path, leaf in flat.items():
        parts = path.split(".")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return root


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def state_fragments(state) -> Dict[str, Dict[str, np.ndarray]]:
    """The in-memory form of a universal checkpoint: {dotted_path: {fp32,
    exp_avg?, exp_avg_sq?}} host numpy fragments pulled from a TrainState
    (or any (params, opt_state) carrier).  Master weights come from the
    optimizer's ``MasterWeightsState`` when present (true fp32 masters,
    reference _create_fp32_partitions), else params are upcast."""
    flat = _flatten_params(state.params)
    opt_state = state.opt_state
    masters = _master_states(opt_state)
    master_flat = _flatten_params(masters[0]["master"]) if masters else flat
    adams = _adam_states(opt_state)
    mu_flat = _flatten_params(adams[0]["mu"]) if adams else None
    nu_flat = _flatten_params(adams[0]["nu"]) if adams else None

    frags: Dict[str, Dict[str, np.ndarray]] = {}
    for p in flat:
        w = np.asarray(jax.device_get(master_flat[p]))
        # bf16 needs the explicit dtype compare — numpy's kind for ml_dtypes
        # bfloat16 is not "f"
        if w.dtype != np.float32 and (w.dtype.kind == "f"
                                      or w.dtype == jax.numpy.bfloat16):
            w = w.astype(np.float32)
        entry = {"fp32": w}
        if mu_flat is not None:
            entry["exp_avg"] = np.asarray(jax.device_get(mu_flat[p]),
                                          np.float32)
            entry["exp_avg_sq"] = np.asarray(jax.device_get(nu_flat[p]),
                                             np.float32)
        frags[p] = entry
    return frags


def write_fragments(frags: Dict[str, Dict[str, np.ndarray]], out_dir: str,
                    *, step: int, layout: Optional[dict] = None,
                    run_dir: Optional[str] = None) -> str:
    """Write fragments to disk under the crash-safe commit protocol
    (marker → fragments + meta durable → marker off → pointer)."""
    from deepspeed_tpu.runtime import faults
    marker = _begin_export(out_dir)
    zdir = os.path.join(out_dir, "zero")
    os.makedirs(zdir, exist_ok=True)
    manifest = {}
    half = len(frags) // 2
    for i, p in enumerate(sorted(frags)):
        if i == half:
            faults.fire("universal.mid_fragments", out_dir=out_dir)
        entry = frags[p]
        d = os.path.join(zdir, p)
        os.makedirs(d, exist_ok=True)
        for key in _FRAGMENT_KEYS:
            if key in entry:
                np.save(os.path.join(d, key + ".npy"),
                        np.asarray(entry[key]))
        w = np.asarray(entry["fp32"])
        manifest[p] = {"shape": list(w.shape), "dtype": str(w.dtype),
                       "has_moments": "exp_avg" in entry}
    _write_meta_json(out_dir, step, manifest, layout)
    return _commit_export(out_dir, marker, run_dir)


def export_universal(state, out_dir: str, *, step: Optional[int] = None,
                     layout: Optional[dict] = None,
                     run_dir: Optional[str] = None) -> str:
    """Write a TrainState (or any (params, opt_state) carrier) as universal
    fp32 fragments under the crash-safe commit protocol.

    ``layout`` (checkpoint/reshard.py layout descriptor) converts the
    source engine's physical parameter layout (e.g. pipeline-stacked
    leaves) into the LOGICAL per-layer namespace before writing, and is
    recorded in meta.json.  ``run_dir`` additionally moves the
    ``latest_universal`` pointer post-commit, making this export the
    fleet's newest COMPLETE resume source."""
    if step is None:
        step = int(jax.device_get(state.step)) if hasattr(state, "step") else 0
    frags = state_fragments(state)
    if layout is not None:
        from deepspeed_tpu.checkpoint import reshard
        frags = reshard.to_logical(frags, layout)
    return write_fragments(frags, out_dir, step=int(step), layout=layout,
                           run_dir=run_dir)


# ---------------------------------------------------------------------------
# import
# ---------------------------------------------------------------------------

def _read_fragment(d: str, key: str):
    """Read one tensor fragment — native ``.npy``, or reference-style torch
    ``.pt`` (checkpoint/ds_to_universal.py writes fp32.pt/exp_avg.pt/...)."""
    from deepspeed_tpu.checkpoint import CheckpointCorrupt
    npy = os.path.join(d, key + ".npy")
    if os.path.exists(npy):
        try:
            return np.load(npy)
        except (OSError, ValueError, EOFError) as e:
            raise CheckpointCorrupt(
                f"{npy}: unreadable fragment ({e}) — torn write?") from e
    pt = os.path.join(d, key + ".pt")
    if os.path.exists(pt):
        import torch
        t = torch.load(pt, map_location="cpu", weights_only=True)
        return t.detach().to(torch.float32).numpy()
    return None


def load_universal(universal_dir: str,
                   name_map: Optional[Callable[[str], Optional[str]]] = None,
                   ) -> Tuple[Dict[str, Dict[str, np.ndarray]], dict]:
    """Read a universal dir → ({dotted_path: {fp32, exp_avg?, exp_avg_sq?}},
    meta).  ``name_map`` renames fragment dirs (e.g. torch module names from a
    reference-produced checkpoint → flax paths); returning None skips one.

    Raises :class:`~deepspeed_tpu.checkpoint.CheckpointNotFound` when the
    dir is not a universal checkpoint, and
    :class:`~deepspeed_tpu.checkpoint.CheckpointCorrupt` when it is one
    whose export never committed (in-progress marker still present) or
    whose fragments are torn — a crashed writer must never be mistaken for
    a resume source."""
    from deepspeed_tpu.checkpoint import (IN_PROGRESS_FILE, CheckpointCorrupt,
                                          CheckpointNotFound)
    if not os.path.isdir(universal_dir):
        raise CheckpointNotFound(
            f"{universal_dir}: no such universal checkpoint dir")
    if os.path.exists(os.path.join(universal_dir, IN_PROGRESS_FILE)):
        raise CheckpointCorrupt(
            f"{universal_dir} carries {IN_PROGRESS_FILE}: its export never "
            f"committed (writer died mid-export) — fragments may be torn.  "
            f"Resume from the previous complete export "
            f"(checkpoint.latest_universal skips this one).")
    zdir = os.path.join(universal_dir, "zero")
    if not os.path.isdir(zdir):
        raise CheckpointNotFound(f"{universal_dir}: no zero/ fragment dir "
                                 "(not a universal checkpoint)")
    frags: Dict[str, Dict[str, np.ndarray]] = {}
    for name in sorted(os.listdir(zdir)):
        d = os.path.join(zdir, name)
        if not os.path.isdir(d):
            continue
        path = name_map(name) if name_map else name
        if path is None:
            continue
        entry = {}
        for key in _FRAGMENT_KEYS:
            arr = _read_fragment(d, key)
            if arr is not None:
                entry[key] = arr
        if "fp32" not in entry:
            raise CheckpointCorrupt(
                f"{d}: no fp32 fragment (.npy or .pt) — torn export?")
        frags[path] = entry
    meta = {}
    mpath = os.path.join(universal_dir, "meta.json")
    if os.path.exists(mpath):
        with open(mpath) as f:
            meta = json.load(f)
    return frags, meta


def apply_universal(state, frags: Dict[str, Dict[str, np.ndarray]],
                    *, strict: bool = True, step: Optional[int] = None):
    """Return a new TrainState with params / masters / Adam moments replaced
    by the fragments (host arrays — caller device_puts with its shardings).

    The fragment set must cover the param tree exactly under ``strict``
    (reference universal_checkpoint.load_hp_checkpoint_state does the same
    per-fragment existence check).  ``step`` also resets the Adam bias-
    correction count — restored mature moments must not be re-bias-corrected
    as if at step 0.
    """
    import optax

    from deepspeed_tpu.runtime.zero import MasterWeightsState

    flat = _flatten_params(state.params)
    missing = [p for p in flat if p not in frags]
    extra = [p for p in frags if p not in flat]
    if strict and (missing or extra):
        raise ValueError(
            f"universal checkpoint does not match the model: missing "
            f"{missing[:4]}{'...' if len(missing) > 4 else ''}, unexpected "
            f"{extra[:4]}{'...' if len(extra) > 4 else ''}")

    def cast_like(arr, like):
        return np.asarray(arr).astype(np.asarray(like).dtype) \
            if hasattr(like, "dtype") else arr

    new_params = _unflatten_params(
        {p: cast_like(frags[p]["fp32"], flat[p]) if p in frags else flat[p]
         for p in flat})

    have_moments = any("exp_avg" in frags.get(p, {}) for p in flat)

    def visit(node):
        if isinstance(node, MasterWeightsState):
            flat_master = _flatten_params(node.master)
            m = _unflatten_params(
                {p: np.asarray(frags[p]["fp32"], np.float32)
                 if p in frags else flat_master[p] for p in flat})
            return MasterWeightsState(
                master=m, inner=_rewrite_nodes(node.inner, visit))
        if isinstance(node, optax.ScaleByAdamState) and have_moments:
            flat_mu = _flatten_params(node.mu)
            flat_nu = _flatten_params(node.nu)

            def moment(p, key, fallback):
                f = frags.get(p)
                if f is not None and key in f:
                    return np.asarray(f[key], np.float32)
                return fallback[p]       # moment-less leaf (e.g. int param)

            mu = _unflatten_params(
                {p: moment(p, "exp_avg", flat_mu) for p in flat})
            nu = _unflatten_params(
                {p: moment(p, "exp_avg_sq", flat_nu) for p in flat})
            count = (node.count if step is None
                     else np.asarray(step, np.asarray(node.count).dtype))
            return optax.ScaleByAdamState(count=count, mu=mu, nu=nu)
        return None

    new_opt = _rewrite_nodes(state.opt_state, visit)
    return state._replace(params=new_params, opt_state=new_opt)


def export_universal_offload(params, offload_opt, out_dir: str, *,
                             step: int = 0, layout: Optional[dict] = None,
                             run_dir: Optional[str] = None) -> str:
    """Export when the masters/moments live host-side in the ZeRO-Offload
    optimizer (runtime/offload.py OffloadAdam) — the reference's
    ds_to_universal likewise pulls fp32 state out of the swap tier."""
    flat = _flatten_params(params)
    sd = offload_opt.state_dict()
    frags: Dict[str, Dict[str, np.ndarray]] = {}
    for path, leaf in flat.items():
        key = path.replace(".", "/")         # offload keys are "/"-joined
        shape = np.asarray(leaf).shape
        if f"{key}::master" in sd:
            frags[path] = {
                "fp32": np.asarray(sd[f"{key}::master"],
                                   np.float32).reshape(shape),
                "exp_avg": np.asarray(sd[f"{key}::m"],
                                      np.float32).reshape(shape),
                "exp_avg_sq": np.asarray(sd[f"{key}::v"],
                                         np.float32).reshape(shape),
            }
        else:                                 # non-trainable leaf
            frags[path] = {"fp32": np.asarray(leaf)}
    if layout is not None:
        from deepspeed_tpu.checkpoint import reshard
        frags = reshard.to_logical(frags, layout)
    return write_fragments(frags, out_dir, step=int(step), layout=layout,
                           run_dir=run_dir)


def offload_state_dict_from_fragments(params,
                                      frags: Dict[str, Dict[str, np.ndarray]],
                                      step: int) -> Dict[str, Any]:
    """Build an OffloadAdam ``load_state_dict`` payload from fragments."""
    sd: Dict[str, Any] = {"step_count": int(step)}
    for path in _flatten_params(params):
        if path not in frags or "exp_avg" not in frags[path]:
            continue
        key = path.replace(".", "/")
        sd[f"{key}::master"] = frags[path]["fp32"].ravel()
        sd[f"{key}::m"] = frags[path]["exp_avg"].ravel()
        sd[f"{key}::v"] = frags[path]["exp_avg_sq"].ravel()
    return sd


# ---------------------------------------------------------------------------
# CLI (reference: ds_to_universal.py script)
# ---------------------------------------------------------------------------

def _restore_ckpt(ckpt_dir: str, tag: Optional[str]):
    """Resolve tag (falling back to the 'latest' file) and restore the orbax
    state on host.  Returns (state, tag) or (None, None) if no tag."""
    from deepspeed_tpu.checkpoint import latest_tag
    import orbax.checkpoint as ocp
    tag = tag or latest_tag(ckpt_dir)
    if tag is None:
        return None, None
    path = os.path.join(os.path.abspath(ckpt_dir), tag, "state")
    return ocp.StandardCheckpointer().restore(path), tag


def _cli(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.checkpoint.universal",
        description="Export an engine checkpoint to universal fp32 fragments "
                    "(reference checkpoint/ds_to_universal.py)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    ex = sub.add_parser("export", help="orbax checkpoint dir -> universal dir")
    ex.add_argument("ckpt_dir")
    ex.add_argument("out_dir")
    ex.add_argument("--tag", default=None)
    ins = sub.add_parser("inspect", help="print a universal dir's manifest")
    ins.add_argument("universal_dir")
    fp32 = sub.add_parser(
        "zero_to_fp32",
        help="orbax checkpoint dir -> ONE consolidated fp32 safetensors "
             "(reference utils/zero_to_fp32.py offline converter)")
    fp32.add_argument("ckpt_dir")
    fp32.add_argument("out_file")
    fp32.add_argument("--tag", default=None)
    args = ap.parse_args(argv)

    if args.cmd == "export":
        state, tag = _restore_ckpt(args.ckpt_dir, args.tag)
        if state is None:
            print(f"no 'latest' file in {args.ckpt_dir}; pass --tag")
            return 1

        class _Carrier:
            pass

        c = _Carrier()
        c.params = state["params"]
        c.opt_state = state["opt_state"]
        c.step = state.get("step", 0)
        export_universal(c, args.out_dir)
        print(f"exported {args.ckpt_dir}@{tag} -> {args.out_dir}")
        return 0
    if args.cmd == "zero_to_fp32":
        import safetensors.numpy
        state, tag = _restore_ckpt(args.ckpt_dir, args.tag)
        if state is None:
            print(f"no 'latest' file in {args.ckpt_dir}; pass --tag")
            return 1
        masters = _master_states(state["opt_state"])
        src = masters[0]["master"] if masters else state["params"]
        flat = {}
        for k, v in _flatten_params(src).items():
            arr = np.asarray(v)
            if arr.dtype != np.float32 and (arr.dtype.kind == "f"
                                            or arr.dtype
                                            == jax.numpy.bfloat16):
                arr = arr.astype(np.float32)
            flat[k] = arr
        os.makedirs(os.path.dirname(os.path.abspath(args.out_file)),
                    exist_ok=True)
        safetensors.numpy.save_file(flat, args.out_file)
        print(f"consolidated {len(flat)} tensors "
              f"({'fp32 masters' if masters else 'params'}) -> "
              f"{args.out_file}")
        return 0
    frags, meta = load_universal(args.universal_dir)
    print(json.dumps({"format": meta.get("format"),
                      "step": meta.get("step"),
                      "num_params": len(frags),
                      "total_elems": int(sum(f["fp32"].size
                                             for f in frags.values()))},
                     indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(_cli())
