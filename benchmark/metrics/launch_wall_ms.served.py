"""Mean wall time of one EC device launch as the dispatcher times it
(the ``ec`` family's ``encode_time``, fed once per launch), over every
launch of the window on every OSD."""


def read(run):
    c = run.counters
    if not c.get("launches"):
        return None
    return 1e3 * c["launch_s"] / c["launches"]
