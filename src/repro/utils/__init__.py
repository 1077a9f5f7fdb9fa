"""Compiled-program cost model (``hlo_cost``) and the device peaks it is
read against (``roofline``), both used by the tile sweep in ``repro.tune``."""
