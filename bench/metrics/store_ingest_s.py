"""Seconds of ``OMSPipeline.ingest`` (encode and write the library store),
host clock, in set-up."""


def read(cell):
    return cell.phases.get("ingest")
