"""Host pipeline: over the operations that follow a landed commit, the
median of the program's `advance.resident_append` span: the delta coded
against the path dictionary, dealt to its shards' free slots, shipped,
sorted again on the four chips, and both masks rebuilt on the host. What
the resident route costs a refresh, the chips' part included. None where
no refresh took it."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "advance.resident_append")
