"""The attention of a train step in a device trace, by operation name.

Two forms of the same work (scores, softmax, the product with V, and
their gradients) are found:

* the fused causal kernel's custom calls (forward, dq and dk/dv), which
  the compiler names after the kernel: ``%splash_mqa_fwd_residuals.17 =
  (...) custom-call(...)``;
* the unfused path's fusions and convolutions that read or write a
  rank-4 score array: (B, H, S, S) where the whole score matrix is
  written, (B, H, 1024, 1024) per block pair of the chunked form.

The projections of q, k, v and the output are in neither.  The scanned
layers' operations appear by name in the trace beside their ``while``,
which is left out."""
import re
from typing import Optional

from chipbench import readers

CHUNK = 1024  # the chunked form's (q, kv) block
_KERNEL = re.compile(r"^%splash_\w+(?:\.\d+)? = .*?\bcustom-call\(")
_OP = re.compile(r"^%\S+ = .*?\s(fusion|convolution)\(")


def score_arrays(seq: int) -> re.Pattern:
    return re.compile(
        rf"\w+\[\d+,\d+,(?:{seq},{seq}|{CHUNK},{CHUNK})\]")


def is_attention_op(op_name: str, scores: re.Pattern) -> bool:
    if _KERNEL.match(op_name):
        return True
    return bool(_OP.match(op_name) and scores.search(op_name))


def attention_ms(rec) -> Optional[float]:
    """Device time of the attention per train step; None where the trace
    holds none."""
    if rec.trace is None:
        return None
    scores = score_arrays(int(rec.traffic["train"]["seq"]))
    s = rec.trace.op_time(lambda name: is_attention_op(name, scores))
    _, steps = rec.trace.module_time(
        lambda name: name.startswith(readers.TRAIN_STEP_PROGRAM))
    return 1e3 * s / steps if s > 0 and steps else None
