"""Median host time of the ``mr.dispatch`` spans that open in the traced
window: ``local_mapreduce``'s wrapper, the compile cache's switch and
the jit's dispatch of one job."""
import statistics


def read(record):
    spans = (record.get("trace") or {}).get("dispatch_us")
    if not spans:
        return None
    return statistics.median(spans)
