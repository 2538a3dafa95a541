"""95th percentile of a screen cell task's execution time.

Source: the ``exec`` spans of the ``repro.obs`` tracer over the cell
tasks (``screen/<cell>/g1``) of every pass in the window.  The threads
backend reports one busy time per message, which the tracer splits
evenly over the message's tasks.
"""

import numpy as np


def read(run):
    d = [e[1] for e in run.events
         if e[2] == "exec" and e[1] >= 0.0 and str(e[5]).startswith(
             "screen/")]
    if not d:
        return None
    return 1000.0 * float(np.percentile(d, 95))
