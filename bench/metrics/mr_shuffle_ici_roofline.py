"""Share of the interconnect roofline the shuffle's all-to-all reaches:
the least bytes that must leave their chip (8 B per valid record owned by
another chip, counted from the reference's records) at the chip's peak
ICI bandwidth, over the all-to-all device time summed over the chips.

Only valid records count, not the padded buffers the all-to-all sends,
so the share is a lower bound; it can read above 100% only if the peak
is wrong."""


def read(record):
    least = record["counters"].get("shuffle_least_bytes")
    a2a = sum(record["counters"].get("all_to_all_s") or [])
    if not least or not a2a:
        return None
    return 100.0 * least / (record["peaks"]["ici_bits_per_s"] / 8) / a2a
