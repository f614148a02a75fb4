"""Each cell's step compiled at its real size for a described v5e: every
cell of BENCHMARK.json, and the rehearsal-only four-chip cells.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  The described topology is made inside a fixture,
never while a module is imported (one process at a time may load the TPU
library).  Prints the compiled memory analysis for PERF.md.
"""
import os

import pytest

from bench import hlo
from bench.tests import tiny

LIMIT_BYTES = 16.9e9        # a v5e chip's bytes_limit as JAX reports it


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no described chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def mosaic(monkeypatch):
    import repro.kernels.ops as kops
    monkeypatch.setattr(kops, "interpret_kernels", lambda: False)


@pytest.mark.parametrize("workload", tiny.accepted(chips=1) + tiny.cells(chips=4))
def test_cell_step_compiles_for_v5e_and_fits(topo, mosaic, workload):
    from bench.program import Cell

    c = tiny.full(workload)
    cell = Cell(c["config"], c["traffic"], topo.devices[:c["chips"]])
    compiled = cell.compile()
    m = compiled.memory_analysis()
    total = hlo.program_bytes(compiled)
    text = compiled.as_text()
    starts = text.count("collective-permute-start(")
    print(f"\n{workload}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / 1e9:.3f} GB, temporaries {m.temp_size_in_bytes / 1e9:.3f} GB,"
          f" total {total / 1e9:.3f} GB; permute starts {starts}, permute bytes "
          f"{hlo.permute_bytes(text) / 1e6:.3f} MB; tpu_custom_call "
          f"{text.count('custom_call_target=\"tpu_custom_call\"')}")
    assert total < LIMIT_BYTES
    if c["chips"] > 1:
        assert hlo.permute_bytes(text) > 0
    else:
        assert hlo.permute_bytes(text) == 0
