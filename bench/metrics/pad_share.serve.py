"""Rows the device computed beyond the requests' rows, in percent of the
requests' rows, from ``PredictEngine.stats`` (queries and padded queries)
and the requests served."""
import layers


def read(reading):
    serve = reading.window.get("serve")
    if not serve or not serve["batches"]:
        return None
    real = sum(rows for rows, _, _ in layers.served_batches(reading))
    eng = serve["engine"]
    return 100.0 * (eng["queries"] + eng["padded_queries"] - real) / real
