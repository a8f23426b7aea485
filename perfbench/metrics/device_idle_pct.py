"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips (1 - union of op intervals / window)."""


def read(t):
    window = t.hi - t.lo
    busy = sum(t.busy.values()) / len(t.busy)
    return 100.0 * (1.0 - busy / window)
