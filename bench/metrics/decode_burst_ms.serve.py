"""Median over the window's decode dispatches of the scheduler's own
``stats['step_walls']`` entry over the tokens of that burst, in ms."""
import numpy as np


def read(rec):
    if rec["kind"] != "serve":
        return None
    per = [w / n for w, n in rec["window"].bursts if n > 0]
    if not per:
        return None
    return float(np.median(per)) * 1e3
