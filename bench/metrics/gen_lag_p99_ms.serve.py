"""99th percentile (ms) of how late the load generator submitted each
query behind its due time. A starved generator must not read as a fast
server."""
import math


def read(view):
    lag = view.result["counts"]["gen_lag_p99_ms"]
    return None if math.isnan(lag) else lag
