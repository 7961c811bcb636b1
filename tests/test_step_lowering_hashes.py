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
the parent's still."""

import os

from lowering_hashes import hashes

PARENT = {
    "mistral-7b-v0.3-16l/mixed": "3c1f760476dc84ef",
    "mistral-7b-v0.3-16l/decode": "1082373fd27e0d39",
    "trinity-large-preview-5l-ep8/mixed": "2f082c1af9f0555b",
    "trinity-large-preview-5l-ep8/decode": "6f71dcd8f7faf3aa",
    "granite-4.0-h-micro/mixed": "722644b634f466bf",
    "granite-4.0-h-micro/decode": "dcb5c7f153d3fd4d",
}


def test_the_accepted_step_programs_lower_as_on_the_parent():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert hashes(root) == PARENT
