"""Device idle share of the serving window: 1 - busy / window, where
busy is the union of the device's op intervals; mean over the chips."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s)
