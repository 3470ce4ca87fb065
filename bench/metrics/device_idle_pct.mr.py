"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""


def read(record):
    tr = record.get("trace")
    if not tr or not tr["idle_pct"]:
        return None
    return sum(tr["idle_pct"]) / len(tr["idle_pct"])
