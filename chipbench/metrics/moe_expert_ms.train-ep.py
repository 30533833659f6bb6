"""moe_expert_ms.train-ep: device time a train step of the held experts'
matmuls (trace).  Their operations are the convolutions, and the fusions
built around one (``kind=kOutput``), with one array out (no tuple: that
is the optimizer's update) of rank 2 or more that read or write an array
shaped like the held experts' weights, (..., n_held, d_model,
moe_width) or (..., n_held, moe_width, d_model): the forward products,
the backward's input and weight gradients.  Fusions that only slice one
layer's weights out of the stack or write a layer's gradient into it
(``kind=kLoop``) move memory and are left out.  The scanned layers'
operations appear by name in the trace beside their ``while``, which
this leaves out."""
import re

from chipbench import readers

_OP = re.compile(r"^%\S+ = (\w+)\[([\d,]*)\]\{[^}]*\} (fusion|convolution)\(")


def _weights(cj) -> re.Pattern:
    n, d = cj["n_routed_experts"], cj["hidden_size"]
    f = cj["moe_intermediate_size"]
    return re.compile(rf"\w+\[(?:\d+,)*{n},(?:{d},{f}|{f},{d})\]")


def is_expert_matmul(op_name: str, weights: re.Pattern) -> bool:
    m = _OP.match(op_name)
    return bool(m and m.group(2).count(",") >= 1
                and (m.group(3) == "convolution" or "kind=kOutput" in op_name)
                and weights.search(op_name))


def read(rec):
    if rec.trace is None:
        return None
    w = _weights(rec.config)
    s = rec.trace.op_time(lambda name: is_expert_matmul(name, w))
    _, steps = rec.trace.module_time(
        lambda name: name.startswith(readers.TRAIN_STEP_PROGRAM))
    return 1e3 * s / steps if s > 0 and steps else None
