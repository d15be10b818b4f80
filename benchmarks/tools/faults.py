"""The faults a cell can have, planted in the program underneath a run: the
timed path is broken and the rest of the run is left as it is, so ``correct``
has to come out false.  ``tests/test_faults.py`` drives them at the rehearsal
size, ``tools/control_run.py --fault`` at a cell's own size on the chip.
"""

import contextlib


@contextlib.contextmanager
def _patched(owner, name, value):
    honest = owner.__dict__[name]
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, honest)


def altered_token():
    """A served token altered where the engine hands it over: the second token
    of every request has its lowest bit flipped."""
    from paddle_tpu.inference import serving
    honest = serving.Request.output.fget

    def altered(self):
        out = honest(self).copy()
        if out.size > 1:
            out[1] ^= 1
        return out

    return _patched(serving.Request, "output", property(altered))


def _step_fault(wrap):
    from paddle_tpu.jit import train_step
    honest = train_step.TrainStep.__call__
    return _patched(train_step.TrainStep, "__call__",
                    lambda self, *inputs: wrap(self, honest, inputs))


def state_unchanged():
    """A step that returns its loss and leaves its parameters where they were."""
    import jax.numpy as jnp

    def wrap(step, honest, inputs):
        before = [jnp.copy(p._value) for p in step._params]
        loss = honest(step, *inputs)
        for p, v in zip(step._params, before):
            p._value = v
        return loss

    return _step_fault(wrap)


def half_batch():
    """Half of the rows left out and the mean taken over the rest; under dp 2
    also what one replica computes when the exchange of gradients between the
    replicas is left out."""
    return _step_fault(lambda step, honest, inputs: honest(
        step, *[x[:x.shape[0] // 2] for x in inputs]))


FAULTS = {"altered_token": altered_token, "state_unchanged": state_unchanged,
          "half_batch": half_batch}
