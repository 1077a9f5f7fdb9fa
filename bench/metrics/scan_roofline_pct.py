"""The scan's share of its roofline: the least time of one query run's scan
(``bench.roofline.least_time``, from the data alone) over the scan
program's device time per run in the trace."""

from bench import harness, roofline
from bench.metrics import scan_device_ms


def read(cell):
    import jax

    work = cell.layer.get("work")
    ms = scan_device_ms.read(cell)
    if work is None or not ms:
        return None
    s = cell.cfg["search"]
    t, bound = roofline.least_time(
        work, cell.cfg["encoding"]["dim"], s["top_k"],
        roofline.peaks_for(jax.devices()[0].device_kind))
    harness.log(f"scan roofline: least time {t * 1e3:.3f} ms ({bound}-bound)"
                f", {work.pairs} pairs, {work.rows} rows, device "
                f"{ms:.3f} ms per run")
    return 100.0 * t / (ms / 1e3)
