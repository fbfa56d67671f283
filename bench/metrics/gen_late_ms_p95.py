"""95th percentile of how late the load generator handed each request
to the served path: send time minus due time."""

import numpy as np


def read(ctx):
    late = ctx.records.get("gen_late_s")
    if late is None or not len(late):
        return None
    return 1e3 * float(np.percentile(late, 95, method="inverted_cdf"))
