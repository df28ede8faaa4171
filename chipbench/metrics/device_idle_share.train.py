"""Share of the traced window in which no operation ran on the device
(mean over chips): 1 - union of device-op intervals / window."""


def read(m):
    d = m.device
    if d is None or d.window_s <= 0:
        return None
    return 100.0 * d.idle_share
