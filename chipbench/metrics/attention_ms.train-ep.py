"""attention_ms.train-ep: device time of the attention per train step
(trace), as ``chipbench.attention_ops`` finds it."""
from chipbench import attention_ops


def read(rec):
    return attention_ops.attention_ms(rec)
