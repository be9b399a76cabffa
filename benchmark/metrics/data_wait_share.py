"""Share of the window's loop time that the train loop spent blocked on
``next(loader)``: the program's own counters (``obs/telemetry.py``
``data_wait_seconds_total`` over ``step_seconds_total``), read before and
after the window."""


def read(evidence, **_):
    c0, c1 = evidence.get("counters0"), evidence.get("counters1")
    if not c0 or not c1:
        return None
    wall = c1["step_seconds_total"] - c0["step_seconds_total"]
    if wall <= 0:
        return None
    wait = c1["data_wait_seconds_total"] - c0["data_wait_seconds_total"]
    return 100.0 * wait / wall
