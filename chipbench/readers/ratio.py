"""One observed number over another, times ``scale``:
``{"num": "work_tokens", "den": "window_s"}``."""


def read(obs: dict, args: dict):
    num, den = obs.get(args["num"]), obs.get(args["den"])
    if num is None or not den:
        return None
    return args.get("scale", 1.0) * num / den
