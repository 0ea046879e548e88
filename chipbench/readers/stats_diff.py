"""From the program's own ``/stats`` snapshot, diffed over the window:
the growth of some histograms' sums over the growth of others' sums or of a
counter.  ``{"num": ["tick_device_wait_seconds"], "den":
["decode_ticks"], "scale": 1000}``.  A histogram contributes its ``sum``,
a counter its value."""


def _grown(stats0: dict, stats1: dict, names) -> float:
    def val(s, n):
        v = s.get(n)
        return float(v["sum"]) if isinstance(v, dict) else float(v or 0.0)

    return sum(val(stats1, n) - val(stats0, n) for n in names)


def read(obs: dict, args: dict):
    s0, s1 = obs.get("stats0"), obs.get("stats1")
    if not s0 or not s1:
        return None
    den = _grown(s0, s1, args["den"])
    if den <= 0:
        return None
    return args.get("scale", 1.0) * _grown(s0, s1, args["num"]) / den
