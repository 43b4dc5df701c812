"""Jitted wrapper for the crossbar INT8 matmul."""
import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.crossbar_mvm.kernel import crossbar_mvm_pallas


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def crossbar_mvm(x_codes, w_codes, zp_x, zp_w, scale, bm: int = 128,
                 bn: int = 128):
    return crossbar_mvm_pallas(x_codes, w_codes, zp_x, zp_w, scale,
                               bm=bm, bn=bn, interpret=interpret_mode())
