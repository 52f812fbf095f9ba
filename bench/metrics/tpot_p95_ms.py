"""95th percentile over the window's requests of the mean time per output
id after the first: the entry's ``decode_s / (N - 1)`` (host clock)."""
import numpy as np


def read(r):
    v = [b.decode_s * 1e3 / (b.generated.shape[1] - 1)
         for b in r.window.batches for _ in range(b.generated.shape[0])]
    return float(np.percentile(v, 95))
