"""One key of the program's ``/stats`` as it stood when the window
closed: a gauge or a high-water mark, which has no growth to diff.
``{"key": "kv_window_pages_per_slot_max"}``; a program without the key
reads as None."""


def read(obs: dict, args: dict):
    value = (obs.get("stats1") or {}).get(args["key"])
    return None if value is None else float(value)
