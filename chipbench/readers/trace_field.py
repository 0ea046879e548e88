"""A number of the trace summary itself: ``{"field": "idle_pct"}`` (1 -
busy union over the traced window) or ``{"field":
"exposed_collective_s", "per": "trace_steps", "scale": 1000}``."""


def read(obs: dict, args: dict):
    tr = obs.get("trace")
    if not tr:
        return None
    if args["field"] == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    value = tr.get(args["field"])
    if value is None:
        return None
    per = obs.get(args["per"]) if "per" in args else 1
    if not per:
        return None
    return args.get("scale", 1.0) * value / per
