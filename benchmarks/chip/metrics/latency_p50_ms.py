"""latency_p50_ms: median, over every read due in the window, of the time
from when it was due to when its answer was back on the host. A read
never answered counts with the whole time it waited."""
import numpy as np


def read(run):
    if len(run.due) == 0:
        return None
    done = np.where(run.answered, run.done, run.gave_up)
    return float(np.percentile(1e3 * (done - run.due), 50))
