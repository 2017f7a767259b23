"""Client ops per device-lane launch of the EC dispatchers, summed over
every OSD over the window (``ec_dispatch.dump()["totals"]``): how far the
dispatcher coalesces concurrent writes into one launch."""


def read(run):
    c = run.counters
    if not c.get("device_batches"):
        return None
    return c["device_ops"] / c["device_batches"]
