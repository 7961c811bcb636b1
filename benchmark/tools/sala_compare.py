#!/usr/bin/env python3
"""The comparison that decides ``correct`` in the cell of a model of lightning
layers beside attention layers that select blocks of keys
(``serve-sala-longdoc-batch``; PERF.md section 6, PR 57; the configuration's
``tolerances.why``):

    python3 benchmark/tools/sala_compare.py --workload <cell>
        [--seeds <n> ...] [--faults <fault> ... | all] [--rehearse]
    python3 benchmark/tools/sala_compare.py --workload <cell> --seed <n>
        --plant <fault> [--rehearse]

It is ``hc_compare.py`` (which see: without ``--plant`` the runner's own two
sequences through the engine against the reference, healthy and with each
fault of ``--faults`` in, one JSON line a reading; with ``--plant`` the
harness itself with the reference swapped for a faulty one, whose last line
must say ``"correct": false``) run over this family's reference and faults:
that tool names ``_xing4`` and ``_xing4_faults`` where it imports them and
nothing of theirs but ``logits``, ``program_config``, ``FAULTS``, ``CONTROL``,
``planted`` and ``planted_reference`` (and ``routing``, with ``--routing``,
which a dense model has no use for), which ``_minicpm_sala`` and
``_sala_faults`` have under the same names.  So, as ``swa_compare.py`` does,
this file hands it those two modules under the names it asks for.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.join(os.path.dirname(HERE), "reference"), HERE):
    sys.path.insert(0, p)

import _minicpm_sala  # noqa: E402
import _sala_faults  # noqa: E402

sys.modules["_xing4"], sys.modules["_xing4_faults"] = (_minicpm_sala,
                                                       _sala_faults)

import hc_compare  # noqa: E402

if __name__ == "__main__":
    sys.exit(hc_compare.main())
