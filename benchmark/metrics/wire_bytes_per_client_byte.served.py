"""Frame bytes that every messenger (OSDs, mon, client) sent per object
byte acknowledged to the client, over the window (msgr ``bytes_send``)."""


def read(run):
    acked = run.work.get("acked_bytes")
    if not acked:
        return None
    return run.counters["bytes_send"] / acked
