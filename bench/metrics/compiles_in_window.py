"""Backend compiles and compile-cache loads inside the window, from
``jax.monitoring`` events."""


def read(cell):
    return cell.layer.get("compiles_in_window")
