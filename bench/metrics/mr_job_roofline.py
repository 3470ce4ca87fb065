"""Share of the HBM roofline the MapReduce jobs reach: the least bytes
their data requires (counted from the reference's records, see
``bench.mrcheck.least_bytes``) at the chip's peak HBM bandwidth, over the
device busy time summed over the cell's chips."""


def read(record):
    least = record["counters"].get("least_bytes")
    tr = record.get("trace")
    if not least or not tr or not tr["busy_s_total"]:
        return None
    return 100.0 * least / record["peaks"]["hbm_bytes_per_s"] / tr[
        "busy_s_total"]
