"""Median host time of a step to block_until_ready, ms."""
import numpy as np


def read(obs):
    return float(np.median(obs["step_s"]) * 1e3)
