"""``python faulty_run.py <fault> <chipbench.run's arguments>``: a run of the
benchmark with the timed path broken underneath, for the tests that have to
see ``correct`` come out false. A process of its own, so that nothing it
patches or configures reaches another test.

``altered_token``: every token is altered where it is produced. The cached
forward pass that the engine's prefill and decode programs call returns its
logits moved on by one place, so the greedy choice is the token after the
right one.
"""

import sys


def altered_token():
    import jax.numpy as jnp

    from accelerate_tpu import generation

    for name, plan in list(generation.GENERATION_PLANS.items()):
        def broken(*args, _plan=plan, **kwargs):
            logits, cache = _plan(*args, **kwargs)
            return jnp.roll(logits, 1, axis=-1), cache

        generation.GENERATION_PLANS[name] = broken


FAULTS = {"altered_token": altered_token, "none": lambda: None}

if __name__ == "__main__":
    import os

    sys.path.insert(0, os.getcwd())
    FAULTS[sys.argv[1]]()
    from chipbench import run

    sys.exit(run.main(sys.argv[2:]))
