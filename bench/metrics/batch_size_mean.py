"""Mean queries per micro-batch in the window: the serve loop's
``MicroBatcher`` counters ``n_queries`` over ``n_batches``."""


def read(cell):
    return cell.layer.get("batch_size_mean")
