"""95th percentile over the window's requests of the time from a request's
batch hand-off to its first id on the device: the entry's ``prefill_s``
(host clock, ending in ``block_until_ready``)."""
import numpy as np


def read(r):
    v = [b.prefill_s * 1e3 for b in r.window.batches
         for _ in range(b.generated.shape[0])]
    return float(np.percentile(v, 95))
