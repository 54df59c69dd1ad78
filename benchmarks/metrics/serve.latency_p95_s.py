"""Client seconds from POST until the finished record is fetched, 95th
percentile over the window's requests (the median and the count are on
an earlier line). A per-layer metric, not an end-to-end one: with as
many closed-loop clients as the batcher stacks the service runs at
capacity, where a tail swings with the smallest change (its quartile
spread over like runs read 14-31%, my chip runs, PR 25). Moves
``req_per_s``."""

import numpy as np


def read(obs):
    r = obs.get("requests")
    return float(np.quantile([x["latency"] for x in r], 0.95)) if r else None
