"""100 x (1 - busy / window) over the traced window: busy is the union of the
intervals in which an operation ran on a chip, averaged over the chips."""


def read(m):
    return 100.0 * m.trace.idle_share()
