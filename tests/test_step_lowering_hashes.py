"""A configuration without a sink and with equal K/V widths takes none of the
branches MiMo-V2-Flash brought (PR 53): the serving step programs of
Mistral-7B, Trinity and granite-4.0-h at their ``rehearsal`` sizes, one mixed
and one decode program each, lower to the text they lowered to on the commit
before (7864eeb: ``tests/lowering_hashes.py --root <its checkout>``, recorded
here).  A later PR that changes what those programs ARE re-records the
hashes and says so; one that only adds a path beside them must leave them.

PR 56 re-recorded Trinity's two: its expert layers permute by counts and
combine by a gather (``moe/layer.py:_expert_ffn_ragged``), which is what
those programs are; Mistral's and granite's, which have no expert layer, are
the parent's still.

PR 57 (MiniCPM-SALA: lightning layers through the state layers' one path, a
selection by blocks, a selection a KV head in the prefill kernel's masked
form, the recurrence kernel with a column a head) holds ALL EIGHT accepted
serving configurations, a case each, to the hashes of its parent (2820e62,
``tests/lowering_hashes.py --root <its checkout>``): the six above are as
they were, and Moonlight's, dots3's, LFM2's, Xing4.0's and MiMo's are
recorded for the first time.

PR 58 re-recorded ``granite-4.0-h-micro/mixed``: the scan of a pass's prompt
chunks is the op ``ssm_pool_chunk_scan`` (``ops/ssm_scan.py``), whose XLA
form (what a CPU lowering takes) is the gather, the scan and the scatter
that ``inference/v2/model.py`` held, now in one place and in another order
(the pool is written before ``y`` is scattered); the other fifteen, LFM2's
mixed step among them (it walks the same loop and holds no state), are the
parent's still."""

import os

import pytest
from lowering_hashes import CONFIGS, hashes

PARENT = {
    "mistral-7b-v0.3-16l/mixed": "3c1f760476dc84ef",
    "mistral-7b-v0.3-16l/decode": "1082373fd27e0d39",
    "trinity-large-preview-5l-ep8/mixed": "2f082c1af9f0555b",
    "trinity-large-preview-5l-ep8/decode": "6f71dcd8f7faf3aa",
    "granite-4.0-h-micro/mixed": "b1d5001bf4a1f5aa",
    "granite-4.0-h-micro/decode": "dcb5c7f153d3fd4d",
    "moonlight-16b-a3b-7l/mixed": "eff6a6cff871d3f8",
    "moonlight-16b-a3b-7l/decode": "c3ac911e409692c5",
    "dots3-note-prev-5l-ep8/mixed": "6b8b2f2310b1a76e",
    "dots3-note-prev-5l-ep8/decode": "b24ccb3814d6c331",
    "lfm2-24b-a2b-10l/mixed": "175efd30c848ee5b",
    "lfm2-24b-a2b-10l/decode": "71305237b56f37db",
    "xing4.0-29b-a4b-7l/mixed": "66f4cccb861aa1e0",
    "xing4.0-29b-a4b-7l/decode": "ab0919732662d05b",
    "mimo-v2-flash-7l-ep16/mixed": "d7e7b10e65812786",
    "mimo-v2-flash-7l-ep16/decode": "9049e59634f77f99",
}


@pytest.mark.parametrize("config", CONFIGS)
def test_the_accepted_step_programs_lower_as_on_the_parent(config):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert hashes(root, (config,)) == {
        k: v for k, v in PARENT.items() if k.startswith(config + "/")}
