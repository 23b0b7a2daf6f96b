"""95th percentile of how late the idle serving loop woke for a due
request, in ms."""
import numpy as np


def read(reading):
    serve = reading.window.get("serve")
    if not serve or len(serve["lags"]) == 0:
        return None
    return float(np.percentile(serve["lags"], 95) * 1e3)
