"""Trainer: median host-clock time of one step in the window (completion to
completion, each read after ``block_until_ready`` of the step's loss)."""
import numpy as np


def read(obs):
    ms = obs.train.get("step_ms")
    return float(np.median(ms)) if ms else None
