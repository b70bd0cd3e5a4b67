"""The control of ``correct`` at a size a test can hold, with no clock in it:
the engine serves fixed prompts to their budgets (``engine.run``), the served
rows go through ``check.served_gaps`` as a run's sample does, and beside the
program's gaps stand those of the reference computed in int8 and put in the
program's place. The program has to pass the cell's (tiny-size) limits and the
control has to fail one of them."""

import numpy as np
import pytest

from chipbench import check, rehearsal, spec, traffic, weights
from chipbench.drivers import serve
from chipbench.stats import RequestRecord


def tiny_readings(cell_name: str, seed: int, n_requests: int = 6) -> tuple[dict, dict]:
    """(numbers, limits) of one tiny engine under one seed."""
    cell = rehearsal.shrink(spec.load_cell(cell_name))
    flat = weights.make_weights(cell.family.weight_specs(cell.config),
                                cell.config["initializer_range"], seed)
    reqs = traffic.make_requests(cell.workload["traffic_params"], cell.config["vocab_size"],
                                 seed, n_requests)
    engine = serve.build_engine(cell, weights.nest(flat), max(r.budget for r in reqs))
    sample = []
    for r in reqs:   # one at a time: each to its own budget
        row = engine.run([r.prompt], max_new_tokens=r.budget)[0]
        sample.append(RequestRecord(index=r.index, phase="window", prompt_len=r.prompt.size,
                                    budget=r.budget, due_s=0.0, submit_s=0.0, status="ok",
                                    new_tokens=r.budget, tokens=np.asarray(row)))
    engine.close()
    correct = cell.workload["correct"]
    numbers = check.served_gaps(cell.family, cell.config, flat, sample,
                                int(cell.workload["engine"]["max_len"]),
                                float(correct.get("router_margin_min", 0.0)), control="int8")
    return numbers, correct["limits"]


@pytest.mark.parametrize("cell,seed", [("mistral_serve_steady", 1), ("mixtral_serve_decode", 1)])
def test_the_program_passes_its_limits_and_the_int8_control_fails_one(cell, seed):
    numbers, limits = tiny_readings(cell, seed)
    assert numbers["tokens"] - numbers["tokens_left_out"] >= 30
    ok, compared = check.judge(numbers, limits)
    assert ok, compared
    control = {k.removeprefix("control_"): v for k, v in numbers.items()
               if k.startswith("control_")}
    ok, compared = check.judge(control, limits)
    assert not ok, compared
