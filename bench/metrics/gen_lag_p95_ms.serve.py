"""How late the load generator sent: 95th percentile of (send time - due
time) over the window's requests, on the generator's own clock."""
import numpy as np


def read(layer):
    lag = layer.quantities.get("send_lag_s")
    if lag is None or len(lag) == 0:
        return None
    return float(np.percentile(lag, 95)) * 1e3
