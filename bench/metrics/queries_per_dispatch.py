"""queries_per_dispatch: requests the front door completed in the
window per engine dispatch (``execute_many`` call), from its
``completed`` and ``batches`` counters."""


def read(run):
    batches = run.door_after["batches"] - run.door_before["batches"]
    if batches <= 0:
        return None
    return (run.door_after["completed"]
            - run.door_before["completed"]) / batches
