"""repro: RapidOMS on TPU — HDC open-modification spectral library search.

Layout, from the entry point down:
  repro.launch.oms  — the CLI: build a store, search it, serve JSON-lines
                      queries, report traces, analyze, tune
  repro.core        — the paper's contribution: OMSPipeline (encode, plan,
                      search, FDR), the planner and blocked dual-window
                      search, HD encoding, decoys, the dimension cascade
  repro.kernels     — Pallas TPU kernels for the Hamming scan and encoding
  repro.store       — the on-disk library store of packed reference HVs
  repro.serve       — the streaming slab engine and the micro-batching
                      serve front end
  repro.obs         — spans, metrics and the trace report
  repro.analysis    — jaxpr contracts and the import-graph check
  repro.tune        — tile sweep and winner cache (uses repro.utils)
  repro.data        — synthetic spectra calibrated to the paper's Table I
  repro.distributed — sharded search over a device mesh (collectives)
"""

__version__ = "1.0.0"
