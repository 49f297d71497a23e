"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the device's op intervals) / window."""


def read(tw):
    busy, window = tw.busy_s(), tw.window_s()
    if busy is None or not window:
        return None
    return (1.0 - busy / window) * 100.0
