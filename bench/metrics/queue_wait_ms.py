"""queue_wait_ms (ms): mean wait in the front door's queue, admission to
dispatch, of the requests it dispatched in the window: the change of its
``queue_wait_s`` counter over the change of its ``dispatched`` counter
(``repro.serve.FrontDoor``, on the door's clock).  A front door without
those counters gives nothing to read."""


def read(run):
    before, after = run.door_before, run.door_after
    if "dispatched" not in before or "dispatched" not in after:
        return None
    dispatched = after["dispatched"] - before["dispatched"]
    if dispatched <= 0:
        return None
    return 1e3 * (after["queue_wait_s"] - before["queue_wait_s"]) / dispatched
