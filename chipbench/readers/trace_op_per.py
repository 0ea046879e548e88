"""Device time of the operations whose trace name matches ``pattern``,
per traced tick or step: ``{"pattern": ..., "per": "trace_ticks",
"scale": 1000}``."""

from chipbench import xplane


def read(obs: dict, args: dict):
    tr, n = obs.get("trace"), obs.get(args["per"])
    if not tr or not n:
        return None
    sec = xplane.op_seconds(tr, args["pattern"])
    if sec <= 0:
        return None
    return args.get("scale", 1.0) * sec / n
