"""Which events of a v5e trace's ``XLA Ops`` line are the program's Pallas
kernels (read by hand from the traces of PR 25).  A Pallas kernel reaches the
trace as an HLO ``custom-call`` named after the scope it was traced in: the
flash kernels of the train step are the ``Attention_0.<n>`` custom-calls, or
``shard_map.<n>`` where they run per shard over a mesh (the step program holds
no other custom-call), and in the serving decode programs
the paged decode kernel is the custom-call of the decode step.  A later PR
that renames a scope adds a reader of its own; the arithmetic stays in
``costs.py``."""

import re

SCOPES = {
    # one chip: the flax scope; over a mesh the kernels sit in a shard_map
    "flash_attention": re.compile(r"^(Attention_0|shard_map)(\.\d+)*$"),
    "paged_decode": None,        # any custom-call of a decode program
}


def is_kernel(trace, op_name, kernel):
    scope = SCOPES[kernel]
    return (trace["opcode"].get(op_name) == "custom-call"
            and (scope is None or bool(scope.search(op_name))))
