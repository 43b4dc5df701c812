import jax

from repro.kernels import interpret_mode
from repro.kernels.pulse_count.kernel import pulse_count_pallas


@jax.jit
def pulse_count(old, new):
    return pulse_count_pallas(old, new, interpret=interpret_mode())
