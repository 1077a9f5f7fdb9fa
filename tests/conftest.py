# NOTE: deliberately NO XLA_FLAGS here — smoke tests and benches must see the
# real single-device CPU; only launch/dryrun.py forces 512 host devices, and
# multi-device tests spawn subprocesses (tests/test_distributed.py).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402


@pytest.fixture
def force_scan_kernel(monkeypatch):
    """Run the vpu scan step through its in-place Pallas kernel (interpreted
    on the CPU) for one test. The lowering is decided when a jitted program
    is traced, so JAX's caches are cleared on both sides."""
    import dataclasses

    import jax

    from repro.core import backends

    vpu = dataclasses.replace(backends.get("vpu"),
                              scan_fits=lambda max_r, n_words: True)
    monkeypatch.setitem(backends._REGISTRY, "vpu", vpu)
    jax.clear_caches()
    yield
    jax.clear_caches()
