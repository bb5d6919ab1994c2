"""The one `pallas_call` every kernel in this package goes through.

Three decisions live here and nowhere else (and the one count every
kernel gets: each call is a place in the programs being traced,
`observability.trace.kernel_place`):

- interpret mode: kernels compile natively (Mosaic) on a TPU backend
  and run in the Pallas interpreter on any other backend, which is how
  the CPU test-suite executes the identical kernel bodies. Call sites
  never compute `interpret=` themselves; a test may still force it.
- x32 tracing: the framework turns jax_enable_x64 on globally for
  paddle dtype parity (framework.py), and under x64 the Python int /
  float literals in index maps and kernel bodies trace as i64/f64,
  which Mosaic cannot legalize. All kernel math is explicitly f32/i32,
  so tracing the call with x64 off is semantics-preserving.
- the kernel's name: `name` is required, and becomes the name of the
  custom call in the compiled program and in a device trace
  (`%flash_fwd.3`). Without one the instruction takes the innermost
  scope's name (`%jvp__.24`), and forward and backward kernels cannot
  be told apart; a new kernel cannot be anonymous.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

from ...observability.trace import kernel_place


def interpret_default() -> bool:
    """True iff kernels must run interpreted: the backend is not a TPU."""
    return jax.default_backend() != "tpu"


def pallas_call(kernel, *, name, interpret=None, **kwargs):
    """`pl.pallas_call` with the package's name, interpret and x32
    decisions applied. interpret=None (every production call site)
    resolves from the backend; True/False is honoured for tests."""
    if not (isinstance(name, str) and name.isidentifier()):
        raise ValueError(f"a Pallas kernel needs an identifier as its "
                         f"name, got {name!r}")
    if interpret is None:
        interpret = interpret_default()
    call = pl.pallas_call(kernel, name=name, interpret=interpret, **kwargs)

    def run(*args):
        # one place in each program being traced (the set-up record's
        # `kernel_places`)
        kernel_place(name)
        with jax.enable_x64(False):
            return call(*args)
    return run
