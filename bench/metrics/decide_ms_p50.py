"""Median wall of one microbatched decide, as the engine records it
(``AsyncRouterEngine.decide_wall_s``; the wall ends when the actions
reach the host)."""
import numpy as np


def read(run):
    walls = run["layer"]["decide_walls_s"]
    return float(np.median(walls)) * 1e3 if walls else None
