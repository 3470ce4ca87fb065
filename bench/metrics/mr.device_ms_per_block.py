"""Device busy time summed over the cell's chips per input block whose
job finished in the traced window."""


def read(record):
    blocks = record["counters"].get("blocks")
    tr = record.get("trace")
    if not blocks or not tr or not tr["busy_s_total"]:
        return None
    return 1e3 * tr["busy_s_total"] / blocks
