"""train_step_ms.train-ep: device time per train step program (trace)."""
from chipbench import readers


def read(rec):
    return readers.program_ms(rec, readers.TRAIN_STEP_PROGRAM)
