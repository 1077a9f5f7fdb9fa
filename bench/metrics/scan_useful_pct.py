"""Share of the (query, row) pairs a search call compares that lie in the
open window: in-window pairs counted by the benchmark from the data
(``bench.roofline.window_work``) over the program's own count of compared
pairs for the call's parameters (``repro.core.search.scanned_rows``)."""


def read(cell):
    work, scanned = cell.layer.get("work"), cell.layer.get("scanned_pairs")
    if work is None or not scanned:
        return None
    return 100.0 * work.pairs / scanned
