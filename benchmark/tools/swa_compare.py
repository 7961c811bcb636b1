#!/usr/bin/env python3
"""The comparison that decides ``correct`` in the cell of a model of window
layers with a sink beside full layers of another key/value geometry
(``serve-mimo-reasoning-batch``; PERF.md section 6, PR 53; the
configuration's ``tolerances.why``):

    python3 benchmark/tools/swa_compare.py --workload <cell>
        [--seeds <n> ...] [--faults <fault> ... | all] [--routing] [--rehearse]
    python3 benchmark/tools/swa_compare.py --workload <cell> --seed <n>
        --plant <fault> [--rehearse]

It is ``hc_compare.py`` (which see: without ``--plant`` the runner's own two
sequences through the engine against the reference, healthy and with each
fault of ``--faults`` in, one JSON line a reading; with ``--plant`` the
harness itself with the reference swapped for a faulty one, whose last line
must say ``"correct": false``) run over this family's reference and faults:
that tool names ``_xing4`` and ``_xing4_faults`` where it imports them and
nothing of theirs but ``logits``, ``routing``, ``program_config``, ``FAULTS``,
``CONTROL``, ``planted`` and ``planted_reference``, which ``_mimo_v2`` and
``_mimo_faults`` have under the same names.  So this file hands it those two
modules under the names it asks for, instead of being its sixth copy.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.join(os.path.dirname(HERE), "reference"), HERE):
    sys.path.insert(0, p)

import _mimo_faults  # noqa: E402
import _mimo_v2  # noqa: E402

sys.modules["_xing4"], sys.modules["_xing4_faults"] = _mimo_v2, _mimo_faults

import hc_compare  # noqa: E402

if __name__ == "__main__":
    sys.exit(hc_compare.main())
