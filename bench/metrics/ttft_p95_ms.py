"""95th percentile, over every request submitted in the window, of the
time from its batch's submission to its first token on the host."""

import numpy as np


def read(rec):
    ttft = [b["times"][0] - b["submit"] for b in rec.batches
            for _ in range(rec.batch)]
    return float(np.percentile(ttft, 95)) * 1e3
