"""estimates_per_s: reads answered by the close of the window, over the
window's length."""
import numpy as np


def read(run):
    return float(np.sum(run.done <= run.seconds)) / run.seconds
