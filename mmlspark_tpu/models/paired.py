"""A Pallas kernel beside its plain form.

Every kernel of the models has a plain XLA form of the same result. The code
takes the kernel where a predicate of what it can observe (the backend, the
dtype, the widths) says it applies, and the plain form elsewhere. No kernel
has a backward pass of its own: ``pair`` gives it the plain form's VJP,
recomputed from the operands. A kernel is traced once for each configuration
and operand shapes and inlined wherever it is called again, so a model of
many alike layers pays one trace of it in its set-up.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

INTERPRET = False   # every pair's kernel in the Pallas interpreter: the CPU tests' seam


def pair(kernel: Callable, plain: Callable, static: int = 0,
         nondiff: Sequence[int] = ()) -> Callable:
    """``f(*operands, *config)``: ``kernel(*operands, *config, interpret=...)``
    forward, the VJP of ``plain(*operands, *config)`` backward. The last
    ``static`` arguments are configuration (hashable: widths, a window, a
    dtype) and pass through as they are; the operands at the positions
    ``nondiff`` (indices, sizes, a selection) take no gradient."""

    @functools.lru_cache(maxsize=None)
    def with_vjp():
        import jax

        # tracing a Pallas kernel is most of tracing a model: once a
        # configuration and shapes, inlined where it is called again
        traced = jax.jit(lambda config, *operands: kernel(
            *operands, *config[1:], interpret=config[0]), static_argnums=(0,), inline=True)

        @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
        def run(config, *operands):          # config: (interpret, *static)
            return traced(config, *operands)

        def fwd(config, *operands):
            return run(config, *operands), operands

        def bwd(config, operands, g):
            wrt = [i for i in range(len(operands)) if i not in nondiff]

            def through(*diff):
                args = list(operands)
                for i, a in zip(wrt, diff):
                    args[i] = a
                return plain(*args, *config[1:])

            grads = dict(zip(wrt, jax.vjp(through, *(operands[i] for i in wrt))[1](g)))
            return tuple(grads.get(i) for i in range(len(operands)))

        run.defvjp(fwd, bwd)
        return run

    def call(*args):
        cut = len(args) - static
        return with_vjp()((INTERPRET, *args[cut:]), *args[:cut])

    return call
