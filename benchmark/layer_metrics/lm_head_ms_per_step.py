"""Device milliseconds a step under the program's `lm_head` scope: the
trunk's final norm, its logits over the held rows of the vocabulary and
the main loss, forward and backward (`benchmark/part_scopes.py` with the
two heads' names as the parts). Beside `mtp_head_ms_per_step`: twice the
other says the logits are computed twice (PERF.md section 7). Absent where
the run is untraced or the program has no such scope."""
from benchmark import part_scopes

LAYER = "head"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
PARTS = ("lm_head",)
HEADS = ("lm_head", "mtp_head")


def read(run):
    return part_scopes.part_ms_per_step(run, PARTS, HEADS)
