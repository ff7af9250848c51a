"""By how much a query is slower at the window's end than at its start: the
median of the last third of a text's queries over that of the first third,
less one, averaged over the texts. A text answered once has no two thirds."""

NAME = "host.window_drift"
UNIT = "%"
LAYER = "served host path"
MOVES = "queries_per_min"
SOURCE = "host_clock"


def read(run):
    shares = [last / first - 1.0
              for first, last, n in run["window"]["thirds"].values() if n >= 2]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
