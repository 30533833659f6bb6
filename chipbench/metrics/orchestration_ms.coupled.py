"""orchestration_ms.coupled: per round, the wall time outside the stage
bodies (each body ends in block_until_ready), from the benchmark spans."""
from chipbench import readers


def read(rec):
    rounds = readers.in_window(rec, "round")
    bodies = sum(sum(readers.in_window(rec, f"stage:{s}"))
                 for s in ("simulate", "analyze", "steer"))
    return 1e3 * (sum(rounds) - bodies) / len(rounds) if rounds else None
