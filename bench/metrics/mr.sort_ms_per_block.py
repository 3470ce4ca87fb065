"""Device time of the engine's ``mr.sort`` stage (``bench.stages``),
summed over the cell's chips, per input block whose job finished in the
traced window."""


def read(record):
    blocks = record["counters"].get("blocks")
    stages = (record.get("trace") or {}).get("stage_s")
    if not blocks or not stages:
        return None
    return 1e3 * stages["sort"] / blocks
