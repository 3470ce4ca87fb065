"""Device time of the shuffle's all-to-all operations, summed over the
cell's chips, per input block whose job finished in the traced window.
The mesh driver reads it from the window's trace (``all_to_all_s``)."""


def read(record):
    blocks = record["counters"].get("blocks")
    a2a = sum(record["counters"].get("all_to_all_s") or [])
    if not blocks or not a2a:
        return None
    return 1e3 * a2a / blocks
