"""Generated ids of every batch the window completed, over the window's wall
time from the first hand-off to the last completion (host clock)."""


def read(r):
    w = r.window
    return sum(b.generated.size for b in w.batches) / w.seconds
