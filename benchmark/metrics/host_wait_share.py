"""Share of the window's loop time in which the train loop waited on the
host loader's queue (the loader's own counter ``input_train_host_wait_
seconds_total`` over ``step_seconds_total``): the input-starved time alone,
without the time the loader waits for the device to take the previous batch
(which ``data_wait_share.train`` includes)."""


def read(evidence, **_):
    c0, c1 = evidence.get("counters0"), evidence.get("counters1")
    key = "input_train_host_wait_seconds_total"
    if not c0 or not c1 or key not in c1:
        return None
    wall = c1["step_seconds_total"] - c0["step_seconds_total"]
    if wall <= 0:
        return None
    return 100.0 * (c1[key] - c0.get(key, 0.0)) / wall
