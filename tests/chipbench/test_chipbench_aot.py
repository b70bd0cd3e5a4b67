"""The serving cells' programs compile for a described v5e chip at the cells'
real sizes and fit its memory: what the chip's compiler would refuse, it
refuses here at no chip time. Nothing runs, so nothing here is a measurement.

The topology is described in a fixture, never while a module is imported, and
only in this file: one process at a time may load the TPU's library."""

import pytest

from chipbench import aot, spec

HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be read back
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module", params=["mistral_serve_steady", "mixtral_serve_decode"])
def programs(request, chip):
    cell = spec.load_cell(request.param)
    return cell, aot.serving_programs(cell, chip)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_cell_s_program_compiles_for_a_v5e_chip_and_fits(programs, program):
    cell, compiled = programs
    m = aot.memory_of(compiled[program])
    assert 0 < m["device_bytes"] < HBM_BYTES
    # the slot cache is donated: the step holds one copy of it, not two
    eng = cell.workload["engine"]
    cache = (2 * 2 * cell.config["num_hidden_layers"] * eng["n_slots"] * eng["max_len"]
             * cell.config["num_key_value_heads"] * cell.config["head_dim"])
    assert m["aliased"] >= cache
    # the weights are arguments at their bf16 size
    assert m["arguments"] >= 2 * cell.family.total_params(cell.config) + cache
