"""Device time of the CRUSH rule program per call: the busy union of the
chip's ops in the traced window over the calls the window made."""


def read(run):
    t, calls = run.trace, run.work.get("calls")
    if not t or not calls or not t["op_events"]:
        return None
    return 1e3 * t["busy_s"] / calls
