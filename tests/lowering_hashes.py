"""The lowered serving step programs of the accepted serving configurations
(all eight since PR 57; three until then) at their ``rehearsal`` sizes,
hashed: what ``tests/test_step_lowering_hashes.py`` holds against the hashes
recorded on the parent commit (a model with none of a PR's new mechanisms must
take none of its new branches: its programs lower to the same text).

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
           "granite-4.0-h-micro", "moonlight-16b-a3b-7l",
           "dots3-note-prev-5l-ep8", "lfm2-24b-a2b-10l",
           "xing4.0-29b-a4b-7l", "mimo-v2-flash-7l-ep16")


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


def hashes(root, configs=CONFIGS):
    sys.path[:0] = [root, os.path.join(root, "benchmark"),
                    os.path.join(root, "benchmark", "reference")]
    import importlib.util

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPTConfig

    out = {}
    for name in configs:
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
