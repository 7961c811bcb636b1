"""The lowered serving step programs of three accepted configurations at
their ``rehearsal`` sizes, hashed: what ``tests/test_mimo_v2_engine.py`` holds
against the hashes recorded on the commit before per-head sinks, unequal K/V
widths and a pool a page group came in (a model with none of them must take
none of the new branches: its programs lower to the same text).

    python3 tests/lowering_hashes.py [--root <checkout>]

prints one JSON object, ``{"<configuration>/<program>": "<sha256[:16]>"}``,
for the checkout at ``--root`` (this one by default): run it on the parent to
record, on the change to compare.  One mixed step and one decode step a
configuration, as ``put()`` builds them (``Lowered.as_text()`` of the jitted
program on the operands the engine handed it)."""

import hashlib
import json
import os
import sys

CONFIGS = ("mistral-7b-v0.3-16l", "trinity-large-preview-5l-ep8",
           "granite-4.0-h-micro")


def record_programs(eng):
    """``eng._steps`` replaced by a dict that wraps each step program as it
    is stored and keeps its first call's operands as shapes: ``{key: (the
    jitted program, its operands' ShapeDtypeStructs)}``, filled as the
    engine dispatches."""
    import jax
    seen = {}

    class Recorder(dict):
        def __setitem__(self, key, fn):
            def call(*args):
                seen.setdefault(key, (fn, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    args)))
                return fn(*args)
            super().__setitem__(key, call)
    eng._steps = Recorder()
    return seen


def hashes(root):
    sys.path[:0] = [root, os.path.join(root, "benchmark"),
                    os.path.join(root, "benchmark", "reference")]
    import importlib.util

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig

    out = {}
    for name in CONFIGS:
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json")) as f:
            cfg = json.load(f)
        cfg = {**cfg, **cfg["rehearsal"],
               "run": {**cfg["run"], **cfg["rehearsal"]["run"]}}
        spec = importlib.util.spec_from_file_location(
            "ref_" + name.replace("-", "_").replace(".", "_"),
            os.path.join(root, cfg["reference"]))
        ref = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ref)
        model_cfg = GPTConfig(**ref.program_config(cfg),
                              max_seq_len=int(cfg["run"]["max_seq_len"]),
                              dropout=0.0, dtype=jnp.bfloat16,
                              attn_impl="pallas")
        eng = InferenceEngineV2(
            model_cfg, {"dtype": "bfloat16",
                        "state_manager": cfg["run"]["state_manager"]},
            seed=0)
        seen = record_programs(eng)
        eng.put([1, 2], [np.arange(40, dtype=np.int32) % 7,
                         np.arange(9, dtype=np.int32) % 5])
        eng.put([1, 2], [np.array([3], np.int32), np.array([4], np.int32)])
        for key, (fn, args) in seen.items():
            kind = key if isinstance(key, str) else key[0]
            text = fn.lower(*args).as_text()
            out[f"{name}/{kind}"] = hashlib.sha256(
                text.encode()).hexdigest()[:16]
    return out


if __name__ == "__main__":
    root = (sys.argv[sys.argv.index("--root") + 1] if "--root" in sys.argv
            else os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps(hashes(os.path.abspath(root)), indent=1))
