"""Seconds of ``OMSPipeline.from_store`` until the library is on the
device (``block_until_ready``), host clock, in set-up."""


def read(cell):
    return cell.phases.get("cold_start")
