"""Compiles a serving cell's programs at their real size for a TPU that is
described and not attached (on-chip-measurement guide, section 2): what the
chip's compiler refuses, for memory or for a kernel, it refuses here at no
chip time. A compile that passes is not a chip run and gives no number.

    JAX_PLATFORMS=cpu python3 -m chipbench.aot --workload <cell> [--set n_slots=32]

The topology is described by the caller (a test's fixture, or ``main`` below):
never while a module is imported.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import sys

from . import spec, weights


def serving_programs(cell, device) -> dict:
    """``{"decode": compiled, "prefill": compiled}``: the engine's decode step
    over every slot and its largest prefill rung, for ``device`` (one of a
    described topology's), from shapes alone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from accelerate_tpu import serving
    from accelerate_tpu.generation import GENERATION_PLANS, init_slot_cache

    place = SingleDeviceSharding(device)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=place)

    def abstract(fn):
        return jax.tree.map(lambda x: shape(x.shape, x.dtype), jax.eval_shape(fn))

    eng = cell.workload["engine"]
    n_slots, max_len = int(eng["n_slots"]), int(eng["max_len"])
    module = cell.family.program_module(cell.config, max_len)
    params = weights.nest({k: shape(s, jnp.bfloat16)
                           for k, (s, _) in cell.family.weight_specs(cell.config).items()})
    fwd = GENERATION_PLANS[type(module).__name__]
    cache = abstract(lambda: init_slot_cache(module.config, n_slots, max_len, dtype=jnp.bfloat16))
    state = abstract(lambda: serving.init_slot_state(n_slots, seed=0, history=16))
    sampling = (0.0, None, None, None)   # greedy, no EOS: as drivers/serve.py builds the engine
    decode = serving._build_decode_step(fwd, module.config, *sampling, speculate_k=0)
    prefill = serving._build_prefill_step(fwd, module.config, *sampling)
    chunk = max(serving.default_prefill_ladder(max_len))
    scalar = shape((), jnp.int32)
    flag = shape((), jnp.bool_)
    return {
        "decode": decode.lower(params, cache, state, shape((n_slots,), jnp.bool_)).compile(),
        "prefill": prefill.lower(params, cache, state, shape((1, chunk), jnp.int32), scalar,
                                 scalar, scalar, abstract(lambda: jax.random.key(0)),
                                 flag, flag).compile(),
    }


def memory_of(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes, "outputs": m.output_size_in_bytes,
            "aliased": m.alias_size_in_bytes, "temporaries": m.temp_size_in_bytes,
            "device_bytes": m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="try another size: n_slots=32, max_len=4096, num_hidden_layers=4")
    args = ap.parse_args(argv)
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    workload, config = copy.deepcopy(cell.workload), dict(cell.config)
    for item in args.set:
        key, value = item.split("=")
        (workload["engine"] if key in workload["engine"] else config)[key] = int(value)
    cell = dataclasses.replace(cell, workload=workload, config=config)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    try:
        programs = serving_programs(cell, topo.devices[0])
    except Exception as e:  # the compiler's refusal is the answer
        print(f"refused: {str(e)[:1500]}")
        return 1
    print(json.dumps({name: memory_of(c) for name, c in programs.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
