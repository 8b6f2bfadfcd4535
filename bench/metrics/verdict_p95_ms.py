"""95th percentile over every verdict step in the window of the time from
handing that cycle's readings to ``ingest()`` to its return, in ms."""

import numpy as np


def read(ctx):
    return float(np.percentile(np.asarray(ctx.window.verdict_s), 95)) * 1e3
