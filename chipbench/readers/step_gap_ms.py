"""Median gap between the trainer's step boundaries in the window."""
import common


def read(obs, params):
    b = obs['boundaries']
    if len(b) < 2:
        return None
    return common.median([y - x for x, y in zip(b, b[1:])]) * 1e3
