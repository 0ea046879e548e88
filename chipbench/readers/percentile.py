"""A percentile of one observed series (linear interpolation, as numpy's
default): ``{"series": "gaps_ms", "q": 95}``; ``min_count`` guards a tail
read from too few samples."""

import numpy as np


def read(obs: dict, args: dict):
    values = obs.get(args["series"])
    if values is None or len(values) < args.get("min_count", 1):
        return None
    return float(np.percentile(np.asarray(values, float), args["q"]))
